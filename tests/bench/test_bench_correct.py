"""``correct``: the drivers run end to end on the CPU at a small size (the
look for a chip skipped), and the comparison passes the sound program and
fails the lower-precision control and each fault the cell can have."""
import dataclasses
import json
import shutil
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import common

serve = common.load_module("drivers", "serve")
plan = common.load_module("drivers", "plan")
cref = common.load_module("reference", "crossbar_plan")


class _Dev:
    def memory_stats(self):
        return {}


def _serve_ctx(seed):
    from repro.configs import get_arch

    arch = dataclasses.replace(get_arch("internlm2-1.8b", reduced=True), dtype="bfloat16")
    conf = {"arch": arch.name, "num_hidden_layers": arch.n_layers, "hidden_size": arch.d_model,
            "num_attention_heads": arch.n_heads, "num_key_value_heads": arch.n_kv_heads,
            "intermediate_size": arch.d_ff, "vocab_size": arch.vocab_size,
            "rope_theta": arch.rope_theta, "rms_norm_eps": 1e-5,
            "assumed": {"head_dim": arch.resolved_head_dim}, "reference": "dense_gqa",
            "engine": {"max_slots": 2, "page_size": 8, "max_seq_len": 64, "prefill_chunk": 16,
                       "decode_quantum": 4, "fused": False}}
    mix = {"generator": "open_loop", "driver": "serve",
           "prompt": {"dist": "uniform", "min": 4, "max": 40},
           "output": {"dist": "uniform", "min": 4, "max": 20}}
    limits = common.load_json("workloads", "chat-internlm2")["limits"]
    cell = {"rate_per_s": 3.0, "drain_s": 60, "limits": limits,
            "sample": {"min_tokens": 48, "max_requests": 4}}
    args = types.SimpleNamespace(seed=seed, seconds=2.0, trace=0)
    return {"args": args, "cell": cell, "config": conf, "traffic": mix, "device": _Dev(),
            "arch": arch, "t_start": time.perf_counter()}


@pytest.fixture(scope="module")
def sound_serve():
    ctx = _serve_ctx(2**31 + 11)
    result, e2e, compared = serve.run(ctx)
    return ctx, result, compared


def test_serving_sound_run_is_correct(sound_serve):
    ctx, result, compared = sound_serve
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] == 6
    assert ctx["compiles_in_window"] == 0


@pytest.mark.parametrize("name", ["queue_wait_ms.serve", "ttft_p50_ms.serve", "step_ms.serve",
                                  "mfu.serve"])
def test_serving_host_readers_read_a_real_window(sound_serve, name):
    """The per-layer readers that need no trace, on what a run's window
    leaves behind (a traced run reads them the same way)."""
    ctx = dict(sound_serve[0], peaks=common.peaks_for("TPU v5 lite"))
    value = common.load_module("metrics", name).read(ctx)
    assert value is not None and 0 < value < (100 if name.startswith("mfu") else 1e5)


_SPY = """
from bench import common

_real = common.load_module("reference", "dense_gqa")
CALLS = []


def dims(conf):
    CALLS.append("dims")
    return _real.dims(conf)


def leaves(dims):
    CALLS.append("leaves")
    return _real.leaves(dims)


class Weights(_real.Weights):
    def __init__(self, dims, seed):
        CALLS.append("Weights")
        super().__init__(dims, seed)


def served_gaps(*args, **kwargs):
    CALLS.append("served_gaps")
    return _real.served_gaps(*args, **kwargs)
"""


def test_the_driver_serves_the_reference_its_configuration_names(tmp_path, monkeypatch):
    """A spy reference, named by a configuration written for the test, is
    the module whose sizes and leaves set the run up and whose weights and
    gaps decide ``correct``: the driver holds no reference of its own."""
    shutil.copytree(common.BENCH, tmp_path / "bench")
    b = tmp_path / "bench"
    (b / "reference" / "spy.py").write_text(_SPY)
    ctx = _serve_ctx(2**31 + 17)
    (b / "configs" / "spy.json").write_text(json.dumps({**ctx["config"], "reference": "spy"}))
    monkeypatch.setattr(common, "BENCH", b)
    ctx["config"] = common.load_json("configs", "spy")
    result, _, compared = serve.run(ctx)
    assert ctx["ref"].__file__ == str(b / "reference" / "spy.py")
    assert ctx["ref"].CALLS == ["dims", "leaves", "Weights", "served_gaps"]
    assert result["correct"], compared


def _long_prompt_ctx(seed):
    """A run's sample as the control reads it, at the reduced width but with
    the chat cell's long prompts (1800 tokens): float8 rounds attention
    probabilities over that many keys to a few levels, as it does at the
    cell's own size (chat reads 3.85-4.66 on the chip).  At the short
    prompts of ``_serve_ctx`` the control reads 0.2-1.1 at this width and
    straddles the limit; over 1800-token prompts it read 0.74-4.55 on 16
    seeds (CPU)."""
    ctx = _serve_ctx(seed)
    vocab = ctx["arch"].vocab_size
    rng = np.random.default_rng(seed)
    recs = [{"rid": i, "ok": True, "prompt": rng.integers(0, vocab, 1800).astype(np.int32),
             "tokens": [int(t) for t in rng.integers(0, vocab, 16)]} for i in range(2)]
    ctx["window"] = {"records": recs}
    ctx["ref"] = serve.reference(ctx["config"])
    ctx["model"] = ctx["ref"].dims(ctx["config"])
    ctx["config"]["engine"]["max_seq_len"] = 1824
    ctx["cell"]["sample"] = {"min_tokens": 32, "max_requests": 2}
    return ctx


def test_serving_control_fails():
    """The control, judged by the chat cell's own rule and limit."""
    ctx = _long_prompt_ctx(2**31 + 13)
    assert ctx["cell"]["limits"] == common.load_json("workloads", "chat-internlm2")["limits"]
    ok, compared = serve.control(ctx, getattr(jnp, serve.CONTROL))
    assert not ok
    assert compared["max_gap"]["value"] > compared["max_gap"]["limit"] == 0.5


def test_serving_reference_in_place_at_full_precision_passes():
    """The same, at float32: the reference agrees with itself (the control's
    failure comes from the precision, not from the harness)."""
    ok, compared = serve.control(_long_prompt_ctx(2**31 + 13), jnp.float32)
    assert ok and compared["max_gap"]["value"] == 0.0, compared


def test_serving_altered_token_fails(monkeypatch):
    from repro.launch import engine

    orig = engine.Engine._append_token

    def altered(self, idx, tok, now):
        slot = self.slots[idx]
        if len(slot.generated) == 2:
            tok = (tok + 1) % self.cfg.vocab_size
        return orig(self, idx, tok, now)

    monkeypatch.setattr(engine.Engine, "_append_token", altered)
    ctx = _serve_ctx(2**31 + 11)
    result, _, compared = serve.run(ctx)
    assert not result["correct"]
    assert compared["max_gap"]["value"] > compared["max_gap"]["limit"]


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def _plan_ctx(seed):
    conf = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 256, "vocab_size": 256,
            "rope_theta": 1e4, "rms_norm_eps": 1e-5, "assumed": {"head_dim": 32}}
    mix = common.load_json("traffic", "layer_redeploy")
    args = types.SimpleNamespace(seed=seed, seconds=1.0, trace=0)
    return {"args": args, "cell": {}, "config": conf, "traffic": mix, "device": _Dev(),
            "t_start": time.perf_counter()}


def test_planner_sound_run_is_correct():
    result, e2e, compared = plan.run(_plan_ctx(2**33 + 1))
    assert result["correct"], compared
    assert e2e["plan_weights_per_s"] > 0


def _plan_control_ctx(seed):
    ctx = _plan_ctx(seed)
    ctx["model"] = common.model_dims(ctx["config"])
    ctx["window"] = {"records": [{"ckpt": 0, "layer": 0}, {"ckpt": 0, "layer": 1}]}
    return ctx


def test_planner_control_fails():
    """The reference on bfloat16-rounded weights, in the program's place,
    judged by the planner cell's own rule."""
    ok, compared = plan.control(_plan_control_ctx(2**33 + 2), getattr(jnp, plan.CONTROL))
    assert not ok
    assert compared["baseline_diff"]["value"] > 0 and compared["off_grid"]["value"] > 0


def test_planner_reference_in_place_at_full_precision_passes():
    """The same, at float32: the reference agrees with itself (the control's
    failure comes from the precision, not from the harness)."""
    ok, compared = plan.control(_plan_control_ctx(2**33 + 2), None)
    assert ok, {k: v for k, v in compared.items() if v["value"]}


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_planner_fault_fails(fault, monkeypatch):
    from repro.core import planner as planner_mod
    from repro.core import pool as pool_mod

    if fault == "state_unchanged":
        orig = pool_mod.CrossbarPool.program

        def program(self, *a, **k):
            state, wear = self._state, self.wear.copy()
            out = orig(self, *a, **k)
            self._state, self.wear = state, wear
            return out

        monkeypatch.setattr(pool_mod.CrossbarPool, "program", program)
    else:
        orig = planner_mod.build_deployment

        def build(*a, **k):
            out = orig(*a, **k)
            name = sorted(out.deployed)[0]
            w = out.deployed[name].copy()
            w.flat[7] = -w.flat[7] if w.flat[7] != 0 else w.flat[8]
            out.deployed[name] = w
            return out

        monkeypatch.setattr(planner_mod, "build_deployment", build)
    result, _, compared = plan.run(_plan_ctx(2**33 + 3))
    assert not result["correct"], compared


def test_planner_reference_holds_exact_zeros():
    """A weight of exactly 0 quantizes to q = 0 with a positive sign bit; a
    stuck low bit may leave it at +scale, and the reference must allow that."""
    import jax

    from repro.core.planner import CrossbarSpec, PlannerConfig, build_deployment
    from repro.core.pool import CrossbarPool

    planner = common.load_json("traffic", "layer_redeploy")["planner"]
    spec = CrossbarSpec(rows=planner["rows"], cols=planner["cols"])
    cfg = PlannerConfig(crossbars=planner["crossbars"], p_stuck=planner["p_stuck"],
                        stuck_cols=planner["stuck_cols"], seed=5)
    pool = CrossbarPool(spec, cfg.crossbars)
    ref_pool = cref.Pool(planner["crossbars"], planner["rows"], planner["cols"])
    rng = np.random.default_rng(0)
    for i in range(3):
        w = rng.standard_normal((128, 256)).astype(np.float32) * 0.05
        w[rng.random(w.shape) < 0.2] = 0.0
        plan_ = build_deployment({"w": jnp.asarray(w)}, spec, cfg, pool=pool)
        r = plan_.reports["w"]
        d = cref.check(ref_pool, jnp.asarray(w), plan_.deployed["w"], {
            "transitions_baseline": r.transitions_baseline,
            "transitions_sws": r.transitions_sws,
            "transitions_final": r.transitions_final}, planner)
        assert d == dict.fromkeys(d, 0), d
    assert np.array_equal(ref_pool.wear, pool.wear)
