"""Crossbar pool programs: device time of ``core/pool``'s programs -- intra-
chain pricing (``_price_intra_packed``), seam pricing (``hamming_pairs``)
and the stucking walk (``_stuck_program_packed``, or
``_full_program_packed`` at p = 1) -- in the traced window, per tensor
planned in it, each ``plan.tensor`` span weighted by the share of it
inside the window; the walk is also found by its scope
(``pool.stuck_walk``).  The time of each program and under that scope, and
the whole per crossbar section (the spans' ``sections``, weighted alike),
are printed on stderr."""
from bench import common, spans as S

PROGRAMS = ("_price_intra_packed", "hamming_pairs", "_stuck_program_packed",
            "_full_program_packed")


WALK = "pool.stuck_walk"  # the walk's scope, which holds if its function is renamed


def _ours(scopes: list, module: str) -> bool:
    return WALK in scopes or any(p in module for p in PROGRAMS)


def read(ctx):
    sp = S.get(ctx)
    if sp is None:
        return None
    lo, hi = ctx["trace"]["window"]
    sec = S.device_seconds(sp, lo, hi, lambda scopes, module, _: _ours(scopes, module))
    n = S.tensors_in(sp, lo, hi)
    if sec is None or not n:
        return None
    parts = {p: S.device_seconds(sp, lo, hi, lambda scopes, m, _, p=p: p in m) for p in PROGRAMS}
    parts[f"scope {WALK}"] = S.device_seconds(sp, lo, hi, lambda scopes, *_: WALK in scopes)
    sections = S.weighted(sp, "plan.tensor", lo, hi, "sections")
    common.log(f"walk_ms: {sec:.6f} s of the pool's programs over {n:.3f} tensors, "
               f"{1e6 * sec / sections if sections else 0:.6f} us a section; "
               + ", ".join(f"{p} {v:.6f} s" for p, v in parts.items() if v is not None))
    return 1e3 * sec / n
