"""Planner set-up inside the pass: the share of the traced window the
window's thread spent in ``plan.compile_prep`` (``core/planner.
_compile_prep_sizes``: lowering and compiling, or loading from the compile
cache, the prep program of every tensor size, on each
``build_deployment``).  On stderr, its calls in the window, the sizes a call
compiles (the span's ``sizes``) and the time per call and per size."""
from bench import common, spans as S, trace as T


def read(ctx):
    sp = S.get(ctx)
    if sp is None or not S.named(sp, "plan.deployment"):
        return None
    lo, hi = ctx["trace"]["window"]
    calls = [s for s in S.named(sp, "plan.compile_prep") if s[2] > lo and s[1] < hi]
    cover = T.union((max(s[1], lo), min(s[2], hi)) for s in calls)
    spent = sum(b - a for a, b in cover)
    sizes = sum(s[4].get("sizes", 0) for s in calls)
    if calls:
        common.log(f"compile_prep: {len(calls)} calls in the window, {sizes / len(calls):.3f} "
                   f"sizes a call, {1e-6 * spent / len(calls):.6f} ms a call, "
                   f"{1e-6 * spent / sizes if sizes else 0:.6f} ms a size")
    w = hi - lo
    return 100.0 * spent / w if w > 0 else None
