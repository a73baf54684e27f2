"""Time to first token as a per-layer reading: the median over requests of
first token - due, as ``serve.end_to_end`` takes it (a request with no
first token counts with the time it had waited when the run ended).

Not end to end: over 49 requests a window the median moves with which
requests queue together and with a stall of the host, 13-15% from seed to
seed on a TPU v5e, more than half of any bound the check allows.  In a
traced run, over the requests due before the profiler started: stopping
it holds the harness's loop for seconds, and the requests due meanwhile
would read that instead."""
from bench import common


def read(ctx):
    win, cut = ctx["window"], ctx.get("trace_from")
    ttft = [(r["first"] if r["first"] is not None else win["end"]) - r["due"]
            for r in win["records"] if cut is None or r["due"] < cut]
    return 1e3 * common.percentile(ttft, 50) if ttft else None
