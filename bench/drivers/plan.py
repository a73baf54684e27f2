"""Planner cells: layer after layer through ``build_deployment(..., pool=pool)``.

Set-up plans one layer made from another seed through a throwaway pool,
which compiles every program the window runs (the per-size prep programs,
pricing, stucking, dequantization).  The window then plans the traffic's
layers in order through one persistent ``CrossbarPool``, as long as a layer
can begin inside it; the layer begun last runs to its end.

``plan_weights_per_s``: weights planned and programmed over the time from
the window's start to the end of the last layer begun inside it, in
millions a second (host clock; ``build_deployment`` returns host results).

``correct`` holds every planned tensor against the plain reference
(``bench/reference/crossbar_plan.py``), which makes the same weights again
from the seed and tracks its own pool: transitions (baseline, SWS, after
stucking), achieved cells, achieved weights, and after the window the wear
of every cell.  Each disagreement is a count; each count's limit is 0.
"""
from __future__ import annotations

import time
import types

import numpy as np

from bench import common
from bench import trace as T
from bench.reference import crossbar_plan as ref

CONTROL = "bfloat16"  # the type the control rounds the weights to (float32 configs)


def _configs(planner: dict, seed: int):
    from repro.core.planner import CrossbarSpec, PlannerConfig

    spec = CrossbarSpec(rows=planner["rows"], cols=planner["cols"], encoding=planner["encoding"])
    cfg = PlannerConfig(sws=planner["sws"], schedule=planner["schedule"],
                        crossbars=planner["crossbars"], p_stuck=planner["p_stuck"],
                        stuck_cols=planner["stuck_cols"], seed=int(seed) & 0x7FFFFFFF)
    return spec, cfg


def run_window(gen, model: dict, mix: dict, seed: int, seconds: float, plan_layer,
               tracer: T.Tracer | None = None, trace_at: tuple[float, float] | None = None):
    """Plan layers while one can begin inside ``seconds``; returns the
    records (checkpoint, layer, reports, achieved weights, end time)."""
    tracer = tracer or T.Tracer("")
    records = []
    order = gen.order(model)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace_at is not None:
            if not tracer.on and now >= trace_at[0]:
                tracer.start()
            elif tracer.on and now >= trace_at[1]:
                tracer.stop()
                trace_at = None
        if now >= seconds:
            break
        ckpt, layer = next(order)
        with tracer.span("bench.plan_layer"):
            params = gen.layer_params(seed, model, mix["drift"], ckpt, layer)
            plan = plan_layer(params)
        records.append({"ckpt": ckpt, "layer": layer, "reports": plan.reports,
                        "deployed": plan.deployed, "end": time.perf_counter() - t0})
    if tracer.on:
        tracer.stop()
    return records


def check(gen, model: dict, mix: dict, seed: int, records: list[dict], wear) -> dict:
    """The reference over every tensor of the window, in order."""
    planner = mix["planner"]
    pool = ref.Pool(planner["crossbars"], planner["rows"], planner["cols"])
    worst: dict[str, int] = {}
    names = list(gen.matrices(model))
    for rec in records:
        got = list(rec["reports"])
        if got != names:
            worst["order"] = worst.get("order", 0) + 1
        for name in names:
            if name not in rec["reports"]:
                continue
            r = rec["reports"][name]
            w = gen.tensor(seed, model, mix["drift"], rec["ckpt"], rec["layer"], name)
            d = ref.check(pool, w, rec["deployed"][name], {
                "transitions_baseline": r.transitions_baseline,
                "transitions_sws": r.transitions_sws,
                "transitions_final": r.transitions_final}, planner)
            for k, v in d.items():
                worst[k] = max(worst.get(k, 0), v)
    worst["wear_diff"] = int(np.max(np.abs(np.asarray(wear, np.int64) - pool.wear)))
    worst.setdefault("order", 0)
    return worst


def run(ctx: dict) -> tuple[dict, dict, dict]:
    from repro.core.planner import build_deployment
    from repro.core.pool import CrossbarPool

    conf, mix, args, cell = ctx["config"], ctx["traffic"], ctx["args"], ctx["cell"]
    model = common.model_dims(conf)
    gen = common.load_module("traffic", mix["generator"])
    spec, pcfg = _configs(mix["planner"], args.seed)

    t = time.perf_counter()
    warm = gen.layer_params(args.seed ^ 0x5A5A5A5A, model, mix["drift"], 0, 0)
    build_deployment(warm, spec, pcfg, pool=CrossbarPool(spec, pcfg.crossbars))
    common.log(f"setup: one warm-up layer planned in {time.perf_counter() - t:.1f} s")
    pool = CrossbarPool(spec, pcfg.crossbars)

    tracer, trace_at = None, None
    if args.trace:
        tracer = T.Tracer(ctx["trace_dir"])
        span = min(cell.get("trace_seconds", 8.0), args.seconds / 2)
        trace_at = (args.seconds / 3, args.seconds / 3 + span)
    ctx["setup_s"] = time.perf_counter() - ctx["t_start"]
    records = run_window(gen, model, mix, args.seed, args.seconds,
                         lambda p: build_deployment(p, spec, pcfg, pool=pool),
                         tracer=tracer, trace_at=trace_at)
    weights = sum(r.n_weights for rec in records for r in rec["reports"].values())
    end = records[-1]["end"]
    common.log(f"window: {len(records)} layers ({weights} weights, "
               f"{sum(len(r['reports']) for r in records)} tensors) planned; last ended "
               f"{end:.2f} s after the window opened; pool writes {pool.total_writes}, "
               f"max per cell {int(pool.wear.max())}")
    ctx["window"] = {"records": records, "weights": weights, "end": end, "seconds": args.seconds}
    ctx["model"] = model
    ctx["memory_peak_bytes"] = int((ctx["device"].memory_stats() or {}).get("peak_bytes_in_use", 0))
    e2e = {"plan_weights_per_s": weights / end / 1e6, "setup_s": ctx["setup_s"]}

    t = time.perf_counter()
    diffs = check(gen, model, mix, args.seed, records, pool.wear)
    common.log(f"reference: {weights} weights checked in {time.perf_counter() - t:.1f} s")
    correct, compared = judge(diffs)
    result = {"correct": correct, "attempted": len(records), "failed": 0 if correct else 1}
    return result, e2e, compared


def judge(diffs: dict) -> tuple[bool, dict]:
    """(correct, compared): every count of disagreements against its limit, 0."""
    compared = {k: {"value": v, "limit": 0} for k, v in diffs.items()}
    return all(v == 0 for v in diffs.values()), compared


def control(ctx: dict, rounding) -> tuple[bool, dict]:
    """The control, judged as a run is: the reference put in the program's
    place on weights rounded to ``rounding``, over the run's own layers in
    order with a pool of its own."""
    import jax

    mix, model, seed = ctx["traffic"], ctx["model"], ctx["args"].seed
    gen = common.load_module("traffic", mix["generator"])
    planner = mix["planner"]
    pool = ref.Pool(planner["crossbars"], planner["rows"], planner["cols"])
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    records = []
    for rec in ctx["window"]["records"]:
        reports, deployed = {}, {}
        for name in gen.matrices(model):
            key, sub = jax.random.split(key)
            w = gen.tensor(seed, model, mix["drift"], rec["ckpt"], rec["layer"], name)
            rep, w_hat = ref.plan(pool, w, planner, sub, rounding=rounding)
            reports[name] = types.SimpleNamespace(**rep, n_weights=w.size)
            deployed[name] = np.asarray(w_hat)
        records.append({"ckpt": rec["ckpt"], "layer": rec["layer"], "reports": reports,
                        "deployed": deployed})
    return judge(check(gen, model, mix, seed, records, pool.wear))
