"""A serving cell's set-up serves exactly what planning and deploying the
same weights would serve, for whatever tree the configuration's reference
declares."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, weights as W

serve = common.load_module("drivers", "serve")
dense_gqa = common.load_module("reference", "dense_gqa")


def _fixture(name):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_test_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


mla_moe = _fixture("mla_moe_reference")


def _model(arch):
    return {"n_layers": arch.n_layers, "d_model": arch.d_model, "n_heads": arch.n_heads,
            "n_kv_heads": arch.n_kv_heads, "head_dim": arch.resolved_head_dim,
            "d_ff": arch.d_ff, "vocab_size": arch.vocab_size, "rope_theta": arch.rope_theta}


def _mla_moe_conf(arch):
    """deepseek-v2-236b's reduced form, under DeepSeek-V2's config.json keys."""
    m, e = arch.mla, arch.moe
    return {"num_hidden_layers": arch.n_layers, "hidden_size": arch.d_model,
            "num_attention_heads": arch.n_heads, "vocab_size": arch.vocab_size,
            "kv_lora_rank": m.kv_lora_rank, "q_lora_rank": m.q_lora_rank,
            "qk_nope_head_dim": m.qk_nope_head_dim, "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "n_routed_experts": e.n_routed,
            "n_shared_experts": e.n_shared, "num_experts_per_tok": e.top_k,
            "moe_intermediate_size": e.d_expert}


def _planned(ref, model, seed):
    """The same weights planned at ``p_stuck = 1`` and deployed packed."""
    from repro.core.planner import CrossbarSpec, PlannerConfig, build_deployment, deploy_params

    key = W.base_key(seed)
    flat = {}
    for leaf in W.layout(ref.leaves(model)):
        if leaf["stacked"]:
            flat[leaf["name"]] = jnp.stack([W.leaf_slice(key, leaf, i)
                                            for i in range(leaf["shape"][0])])
        else:
            flat[leaf["name"]] = W.leaf_slice(key, leaf, 0)
    dense = serve._nest(flat)
    plan = build_deployment(dense, CrossbarSpec(), PlannerConfig())
    assert plan.reports and all(r.quant_mse == 0.0 for r in plan.reports.values())
    return deploy_params(dense, plan, materialize="packed")


def _assert_same_bytes(got, want):
    got_l, got_t = jax.tree_util.tree_flatten_with_path(got)
    want_l, want_t = jax.tree_util.tree_flatten_with_path(want)
    assert got_t == want_t
    for (path, a), (_, b) in zip(got_l, want_l):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert a.tobytes() == b.tobytes(), jax.tree_util.keystr(path)


@pytest.mark.parametrize("name", ["internlm2-1.8b", "yi-6b"])
def test_served_operands_equal_planned_deployment(name):
    from repro.configs import get_arch

    arch = get_arch(name, reduced=True)
    model = _model(arch)
    seed = 2**32 + 17
    served = serve.served_params(arch, dense_gqa, model, seed)
    _assert_same_bytes(served, _planned(dense_gqa, model, seed))


def test_grid_weights_sit_on_the_grid():
    model = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2, "head_dim": 64,
             "d_ff": 512, "vocab_size": 300}
    key = W.base_key(3)
    for leaf in W.layout(dense_gqa.leaves(model)):
        w = np.asarray(W.leaf_slice(key, leaf, 0))
        if leaf["grid"]:
            q = np.abs(w) / leaf["step"]
            assert np.array_equal(q, np.round(q)) and q.max() == W.LEVELS
        assert np.isfinite(w).all()


# The dense layout as the harness drew it before the reference module
# declared its leaves: (name, shape, stacked, grid, gain, step).
_GAIN, _S = 2.0**-10, 2.0**-14
DENSE_LAYOUT = {
    "internlm2-1.8b": [
        ("embed/table", (92544, 2048), False, False, False, _GAIN),
        ("final_norm/g", (2048,), False, False, True, _GAIN),
        ("head/w", (2048, 92544), False, True, False, _S),
        ("segments/0/attn/wk", (24, 2048, 1024), True, True, False, _S),
        ("segments/0/attn/wo", (24, 2048, 2048), True, True, False, _S),
        ("segments/0/attn/wq", (24, 2048, 2048), True, True, False, _S),
        ("segments/0/attn/wv", (24, 2048, 1024), True, True, False, _S),
        ("segments/0/ln1/g", (24, 2048), True, True, True, _GAIN),
        ("segments/0/ln2/g", (24, 2048), True, True, True, _GAIN),
        ("segments/0/mlp/wi_gate", (24, 2048, 8192), True, True, False, _S),
        ("segments/0/mlp/wi_up", (24, 2048, 8192), True, True, False, _S),
        ("segments/0/mlp/wo", (24, 8192, 2048), True, True, False, _S / 2),
    ],
    "yi-6b": [
        ("embed/table", (64000, 4096), False, False, False, _GAIN),
        ("final_norm/g", (4096,), False, False, True, _GAIN),
        ("head/w", (4096, 64000), False, True, False, _S),
        ("segments/0/attn/wk", (32, 4096, 512), True, True, False, _S),
        ("segments/0/attn/wo", (32, 4096, 4096), True, True, False, _S),
        ("segments/0/attn/wq", (32, 4096, 4096), True, True, False, _S),
        ("segments/0/attn/wv", (32, 4096, 512), True, True, False, _S),
        ("segments/0/ln1/g", (32, 4096), True, True, True, _GAIN),
        ("segments/0/ln2/g", (32, 4096), True, True, True, _GAIN),
        ("segments/0/mlp/wi_gate", (32, 4096, 11008), True, True, False, _S),
        ("segments/0/mlp/wi_up", (32, 4096, 11008), True, True, False, _S),
        ("segments/0/mlp/wo", (32, 11008, 4096), True, True, False, _S / 2),
    ],
}


@pytest.mark.parametrize("name", sorted(DENSE_LAYOUT))
def test_dense_layout_is_unchanged(name):
    conf = common.load_json("configs", name)
    assert conf["reference"] == "dense_gqa"
    lay = W.layout(dense_gqa.leaves(dense_gqa.dims(conf)))
    got = [(l["name"], l["shape"], l["stacked"], l["grid"], l["gain"], l["step"]) for l in lay]
    assert got == DENSE_LAYOUT[name]


def test_check_and_mfu_read_as_before():
    """Two numbers computed on the CPU before the reference module owned the
    architecture, pinned: the reference's widest gap over a fixed sample,
    and ``mfu.serve`` over a fixed window."""
    from repro.configs import get_arch

    arch = get_arch("internlm2-1.8b", reduced=True)
    conf = {"num_hidden_layers": arch.n_layers, "hidden_size": arch.d_model,
            "num_attention_heads": arch.n_heads, "num_key_value_heads": arch.n_kv_heads,
            "intermediate_size": arch.d_ff, "vocab_size": arch.vocab_size,
            "rope_theta": arch.rope_theta, "rms_norm_eps": 1e-5,
            "assumed": {"head_dim": arch.resolved_head_dim}, "reference": "dense_gqa"}
    ref = serve.reference(conf)
    rng = np.random.default_rng(7)
    picked = [{"prompt": rng.integers(0, arch.vocab_size, n).astype(np.int32),
               "tokens": [int(t) for t in rng.integers(0, arch.vocab_size, m)]}
              for n, m in ((5, 3), (20, 8), (40, 12))]
    assert serve.check(ref, ref.dims(conf), 2**31 + 5, picked, 64) == 7.653975963592529

    def rec(due, n, prompt):
        return {"rid": 0, "due": due, "tokens": [1] * n, "max_new": n, "ok": n > 0,
                "prompt": np.zeros(prompt, np.int32)}

    model = {"n_layers": 24, "d_model": 2048, "n_heads": 16, "n_kv_heads": 8,
             "head_dim": 128, "d_ff": 8192, "vocab_size": 92544}
    ctx = {"model": model, "config": {"reference": "dense_gqa"},
           "peaks": common.peaks_for("TPU v5 lite"),
           "window": {"records": [rec(0.0, 3, 4), rec(1.0, 1, 10), rec(2.0, 0, 4)],
                      "steps": [(0.0, 0.5), (2.0, 2.25)], "seconds": 51.0}}
    assert common.load_module("metrics", "mfu.serve").read(ctx) == 0.03373913041218274


# ---------------------------------------------------------------------------
# A tree of another architecture, declared by a reference module that is
# only a new file
# ---------------------------------------------------------------------------

def _mla_moe():
    from repro.configs import get_arch

    arch = get_arch("deepseek-v2-236b", reduced=True)
    return arch, mla_moe.dims(_mla_moe_conf(arch))


def test_mla_moe_tree_is_served_from_its_reference():
    from repro.core import simulator

    arch, model = _mla_moe()
    served = serve.served_params(arch, mla_moe, model, 2**33 + 7)
    seg = served["segments"][0]
    e, L = model["n_experts"], model["n_layers"]
    for name in ("wi_gate", "wi_up", "wo"):
        op = seg["moe"][name]
        assert simulator.is_cim_operands(op)
        assert op["planes_packed"].shape[:3] == (L, e, W.COLS)
    assert not simulator.is_cim_operands(seg["mla"]["wk_b"])  # served dense by the program
    assert seg["moe"]["router"].shape == (L, model["d_model"], e)  # too small to deploy


def test_mla_moe_served_operands_equal_planned_deployment():
    arch, model = _mla_moe()
    seed = 2**33 + 7
    _assert_same_bytes(serve.served_params(arch, mla_moe, model, seed),
                       _planned(mla_moe, model, seed))


def test_expert_steps_take_k_as_fan_in():
    _, model = _mla_moe()
    lay = {l["name"]: l for l in W.layout(mla_moe.leaves(model))}
    e, d, de = model["n_experts"], model["d_model"], model["d_expert"]
    for name, k in (("wi_gate", d), ("wi_up", d), ("wo", de)):
        leaf = lay[f"segments/0/moe/{name}"]
        assert leaf["grid"] and leaf["slice"][0] == e and leaf["slice"][-2] == k
        assert leaf["step"] == W.grid_step(k) != W.grid_step(e)
    # one draw of a whole expert stack: q = 1023 at its element 0 of layer 0 only
    leaf = lay["segments/0/moe/wo"]
    key = W.base_key(11)
    q0 = np.abs(np.asarray(W.leaf_slice(key, leaf, 0))) / leaf["step"]
    q1 = np.abs(np.asarray(W.leaf_slice(key, leaf, 1))) / leaf["step"]
    assert q0.shape == (e, de, d) and q0[0, 0, 0] == W.LEVELS
    assert q0.reshape(-1)[1:].max() < W.LEVELS and q1.max() < W.LEVELS


class _Altered:
    """``mla_moe`` with one leaf dropped or given another shape."""

    def __init__(self, drop=None, reshape=None):
        self.__name__ = "altered mla_moe"
        self.drop, self.reshape = drop, reshape

    def leaves(self, model):
        out = []
        for name, shape, stacked in mla_moe.leaves(model):
            if name == self.drop:
                continue
            if name == self.reshape:
                shape = shape[:-2] + (shape[-1], shape[-2])
            out.append((name, shape, stacked))
        return out


@pytest.mark.parametrize("alter", [{"drop": "segments/0/moe/wo"},
                                   {"reshape": "segments/0/moe/wi_up"},
                                   {"reshape": "segments/0/mla/wq_b"}])
def test_a_layout_that_is_not_the_program_tree_is_refused(alter):
    arch, model = _mla_moe()
    with pytest.raises(ValueError, match="parameter tree is not the layout"):
        serve.served_params(arch, _Altered(**alter), model, 3)
