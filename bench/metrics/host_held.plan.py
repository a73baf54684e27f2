"""Planner host code holding the chip idle: the share of the traced window in
which no op ran on the device while the innermost span open on the
window's thread was planner or pool code (``plan.*``, ``pool.*``) other
than a wait on a device result (``*.readback``).

Every idle stretch is printed on stderr by its innermost span: the
planner's and the pool's own, their read-backs, the harness's work outside
``plan.deployment`` (``bench.*``, the weights of the next layer), and what
no span covers; and the pool's share of it per chain it programs (the
``chains`` of the ``pool.program`` spans, each weighted by its share in the
window).  Spans on other threads (the prep programs' compile workers) are
host work, printed beside it, but cover no idle stretch."""
from bench import common, spans as S, trace as T


def read(ctx):
    sp = S.get(ctx)
    if sp is None or not S.named(sp, "plan.deployment"):
        return None
    tr = ctx["trace"]
    lo, hi = tr["window"]
    w = T.window_s(tr)
    idle = S.idle_by_span(sp, tr)
    held = {n: v for n, v in idle.items()
            if n.startswith(("plan.", "pool.")) and not n.endswith(".readback")}
    groups = {
        "planner and pool host code": sum(held.values()),
        "read-backs": sum(v for n, v in idle.items() if n.endswith(".readback")),
        "harness outside plan.deployment": sum(v for n, v in idle.items()
                                               if n.startswith("bench.")),
        S.NO_SPAN: idle.get(S.NO_SPAN, 0.0),
    }
    total = sum(idle.values())
    common.log(f"host_held: device idle {total:.6f} s of a {w:.6f} s window; "
               + "; ".join(f"{g} {v:.6f} s ({100 * v / total if total else 0:.1f}%)"
                           for g, v in groups.items()))
    for n, v in sorted(idle.items(), key=lambda kv: -kv[1]):
        common.log(f"host_held: idle under {n}: {v:.6f} s")
    chains = S.weighted(sp, "pool.program", lo, hi, "chains")
    if chains:
        pool = sum(v for n, v in held.items() if n.startswith("pool."))
        common.log(f"host_held: pool host code {1e6 * pool / chains:.3f} us idle a chain, "
                   f"over {chains:.3f} chains")
    other = {}
    for s in S.program(sp):
        if s[3] != sp["main"]:
            other[s[0]] = other.get(s[0], 0.0) + max(0, min(s[2], hi) - max(s[1], lo)) / 1e9
    for n, v in sorted(other.items(), key=lambda kv: -kv[1]):
        common.log(f"host_held: on other threads (no cover): {n} {v:.6f} s")
    return 100.0 * groups["planner and pool host code"] / w if w > 0 else None
