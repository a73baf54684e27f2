"""Planner prep program: device time of ``core/planner._prep_core_pool``
(quantize, baseline pricing, the SWS sort, packing) in the traced window,
per tensor planned in it -- each ``plan.tensor`` span weighted by the share
of it inside the window.  The time under each of the program's scopes
(``plan.quantize``, ``plan.price_baseline``, ``plan.sws_sort``,
``plan.pack``), the same time per weight (the spans' ``n_weights``, weighted
alike), and the device time of every program in the window, are printed on
stderr."""
from bench import common, spans as S

PROGRAM = "_prep_core_pool"
SCOPES = ("plan.quantize", "plan.price_baseline", "plan.sws_sort", "plan.pack")


def read(ctx):
    sp = S.get(ctx)
    if sp is None:
        return None
    lo, hi = ctx["trace"]["window"]
    sec = S.device_seconds(sp, lo, hi, lambda scopes, module, _: PROGRAM in module)
    n = S.tensors_in(sp, lo, hi)
    if sec is None or not n:
        return None
    parts = {c: S.device_seconds(sp, lo, hi, lambda scopes, m, _, c=c: PROGRAM in m and c in scopes)
             for c in SCOPES}
    weights = S.weighted(sp, "plan.tensor", lo, hi, "n_weights")
    common.log(f"prep_ms: {sec:.6f} s of {PROGRAM} over {n:.3f} tensors, "
               f"{1e9 * sec / weights if weights else 0:.6f} ns a weight; by scope "
               + ", ".join(f"{c} {v:.6f} s" for c, v in parts.items() if v is not None))
    by_program = {}
    for ops in sp["ops"].values():
        for name, start, dur, _, module in ops:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a and not S.is_container(name):
                by_program[module] = by_program.get(module, 0.0) + (b - a) / 1e9
    common.log("prep_ms: device seconds by program in the window: " + ", ".join(
        f"{m or '?'} {v:.6f}" for m, v in sorted(by_program.items(), key=lambda kv: -kv[1])[:10]))
    return 1e3 * sec / n
