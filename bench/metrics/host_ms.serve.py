"""Engine scheduler, from the program's own spans: the mean host time of an
``engine.step`` that dispatched (holds an ``engine.*.dispatch`` span), less
the time its ``*.readback`` spans cover -- admission, building the host
arrays, the jitted calls and the token bookkeeping, without the waits on the
device.  Over the steps that lie inside the traced window.

On stderr, that time split by phase (``engine.admit``; ``prepare``,
``dispatch`` and ``commit`` of every dispatch; the rest), the requests
admitted, and the live and waiting requests a step began with (the
``engine.step`` and ``engine.admit`` counters)."""
from bench import common, spans as S

PHASES = ("engine.admit", ".prepare", ".dispatch", ".commit")


def steps(sp: dict, lo: int, hi: int) -> list[dict]:
    """Each dispatching ``engine.step`` inside [lo, hi): its host ns outside
    the read-backs (``own``), split by ``PHASES`` and ``other``, and its
    counters."""
    out = []
    for step in S.named(sp, "engine.step"):
        if not (lo <= step[1] and step[2] <= hi):
            continue
        kids = S.inside(sp, step)
        if not any(k[0].endswith(".dispatch") for k in kids):
            continue
        own = S.self_ns(step, [k for k in kids if k[0].endswith(".readback")])
        rec = {p: sum(k[2] - k[1] for k in kids if k[0].endswith(p)) for p in PHASES}
        rec.update(own=own, other=own - sum(rec.values()),
                   admitted=sum(k[4].get("admitted", 0) for k in kids if k[0] == "engine.admit"),
                   live=step[4].get("live", 0), waiting=step[4].get("waiting", 0))
        out.append(rec)
    return out


def read(ctx):
    sp = S.get(ctx)
    if sp is None:
        return None
    recs = steps(sp, *ctx["trace"]["window"])
    if not recs:
        return None
    n = len(recs)
    mean = {k: sum(r[k] for r in recs) / n for k in recs[0]}
    common.log(f"host_ms: {n} dispatching steps; host ms a step: "
               + ", ".join(f"{p.split('.')[-1]} {1e-6 * mean[p]:.6f}" for p in (*PHASES, "other"))
               + f"; {sum(r['admitted'] for r in recs)} admitted; a step began with"
               f" {mean['live']:.3f} live and {mean['waiting']:.3f} waiting")
    return 1e-6 * mean["own"]
