"""The program's profiler spans, on a CPU trace read by ``bench/spans.py``.

A few ``Engine.step`` calls (split dispatch) and one ``build_deployment``
through a ``CrossbarPool`` run under ``jax.profiler``; the trace must hold
the span tree the benchmark's readers walk, with counters that agree with
what the program counts itself.  Nothing here is a time.
"""
from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.planner import CrossbarSpec, PlannerConfig, build_deployment
from repro.core.pool import CrossbarPool
from repro.launch.engine import Engine, EngineConfig, Request
from repro.models import api

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common  # noqa: E402
from bench import spans as S  # noqa: E402

# every span the readers walk, and the span it must lie in (same thread)
PARENT = {
    "engine.admit": "engine.step",
    "engine.prefill": "engine.step",
    "engine.decode": "engine.step",
    **{f"engine.{d}.{p}": f"engine.{d}" for d in ("prefill", "decode")
       for p in ("prepare", "dispatch", "readback", "commit")},
    "plan.compile_prep": "plan.deployment",
    "plan.tensor": "plan.deployment",
    "plan.prep": "plan.tensor",
    "pool.program": "plan.tensor",
    "plan.dequant": "plan.tensor",
    "plan.report": "plan.tensor",
    "plan.deployed.readback": "plan.tensor",
    **{f"pool.{p}": "pool.program" for p in (
        "price_intra", "price_intra.readback", "assign", "seam", "seam.readback",
        "walk", "walk.readback", "commit")},
}
SPEC = CrossbarSpec(rows=64, cols=8)


def _requests(base: int) -> list[Request]:
    return [Request(rid=base + i, prompt=np.arange(1, n + 1) % 50, max_new_tokens=9,
                    greedy=True, seed=i) for i, n in enumerate((5, 7, 11))]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = get_arch("gemma-2b", reduced=True)
    eng = Engine(cfg, api.init(jax.random.PRNGKey(0), cfg), EngineConfig(
        max_slots=4, page_size=8, max_seq_len=48, prefill_chunk=16, decode_quantum=4,
        fused=False))
    eng.run(_requests(100))  # compile every shape the traced steps use
    params = {
        "a": {"w": jax.random.normal(jax.random.PRNGKey(0), (96, 64)) * 0.02},
        "b": {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 100)) * 0.02},
    }
    pcfg = PlannerConfig(p_stuck=0.5, min_size=1024, crossbars=8)
    build_deployment(params, SPEC, pcfg, pool=CrossbarPool(SPEC, 8))

    log_dir = str(tmp_path_factory.mktemp("trace"))
    before = dict(eng.stats)
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for r in _requests(200):
            eng.submit(r)
        steps = 0
        while eng.waiting or any(s is not None for s in eng.slots):
            eng.step(0.0)
            steps += 1
        plan = build_deployment(params, SPEC, pcfg, pool=CrossbarPool(SPEC, 8))
    jax.profiler.stop_trace()
    delta = {k: eng.stats[k] - before[k] for k in before}
    return S.load(log_dir), delta, steps, plan


def test_span_tree_nests_as_named(traced):
    sp, _, steps, _ = traced
    assert len(S.named(sp, "engine.step")) == steps
    for name, parent in PARENT.items():
        got = S.named(sp, name)
        assert got, f"no {name} span"
        outer = S.named(sp, parent)
        for s in got:
            assert any(p[1] <= s[1] and s[2] <= p[2] for p in outer), (name, parent)
    # the prep programs compile on worker threads: never the window's thread
    workers = [s for s in sp["spans"] if s[0] == "plan.compile_prep.size"]
    assert len(workers) == 2 and all(s[3] != sp["main"] for s in workers)


def test_decode_args_add_up_to_the_engine_stats(traced):
    sp, delta, _, _ = traced
    dec = [s[4] for s in S.named(sp, "engine.decode") if "rows" in s[4]]
    assert len(dec) == delta["decode_dispatches"] > 0
    assert sum(a["rows"] for a in dec) == delta["decode_rows_live"]
    assert sum(a["rows_padded"] for a in dec) == delta["decode_rows_padded"] > 0
    assert {"pages", "q"} <= set(dec[0])
    pre = [s[4] for s in S.named(sp, "engine.prefill") if "rows" in s[4]]
    assert len(pre) == delta["prefill_dispatches"]
    assert sum(a["tokens"] for a in pre) == 5 + 7 + 11
    assert sum(s[4]["admitted"] for s in S.named(sp, "engine.admit")) == 3
    ctx = {"trace": {"window": S.named(sp, "bench.window")[0][1:3]}, "spans": sp}
    share = common.load_module("metrics", "decode_pad_share.serve").read(ctx)
    assert share == pytest.approx(100.0 * delta["decode_rows_padded"] / (
        delta["decode_rows_live"] + delta["decode_rows_padded"]))
    assert common.load_module("metrics", "host_ms.serve").read(ctx) > 0


def test_one_plan_tensor_per_tensor(traced):
    sp, _, _, plan = traced
    tensors = S.named(sp, "plan.tensor")
    assert len(tensors) == len(plan.reports) == 2
    assert sorted(s[4]["n_weights"] for s in tensors) == sorted(
        r.n_weights for r in plan.reports.values())
    assert sorted(s[4]["sections"] for s in tensors) == sorted(
        r.n_sections for r in plan.reports.values())
    # one pool program per tensor, over as many chains as crossbars it can use
    programs = S.named(sp, "pool.program")
    assert sorted(s[4]["chains"] for s in programs) == sorted(
        min(8, r.n_sections) for r in plan.reports.values())
    (dep,) = S.named(sp, "plan.deployment")
    assert dep[4] == {}
    (prep,) = S.named(sp, "plan.compile_prep")
    assert prep[4] == {"sizes": 2}


def test_no_program_span_is_named_like_the_harness(traced):
    sp, _, _, _ = traced
    assert {s[0] for s in sp["spans"] if s[0].startswith("bench.")} == {"bench.window"}
    assert all(s[0].startswith(S.PROGRAM) for s in S.program(sp))
