"""Engine admission: mean time from a request's due time to its admission
into a slot (``RequestResult.t_admitted``, on the clock the harness hands
``Engine.step``), over every request of the run that was admitted.

In a traced run, over the requests due before the profiler started:
stopping it holds the harness's loop for seconds (up to 19.4 s on a TPU v5e
host), and the requests due meanwhile would read that instead."""


def read(ctx):
    cut = ctx.get("trace_from")
    waits = [r["admitted"] - r["due"] for r in ctx["window"]["records"]
             if r["admitted"] is not None and (cut is None or r["due"] < cut)]
    return 1e3 * sum(waits) / len(waits) if waits else None
