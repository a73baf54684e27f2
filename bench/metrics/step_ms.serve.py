"""Engine scheduler: mean host time of an ``Engine.step`` that dispatched,
from the harness's own span around the call (the step reads its tokens
back, so the span ends when the dispatch has finished).

In a traced run, over the steps that ended before the profiler started,
whose batches the profiler's stop (a stall of seconds) has not yet
swollen."""


def read(ctx):
    cut = ctx.get("trace_from")
    steps = [(a, b) for a, b in ctx["window"]["steps"] if cut is None or b <= cut]
    return 1e3 * sum(b - a for a, b in steps) / len(steps) if steps else None
