"""Device: share of the traced window in which no operation ran on the chip."""
from bench import trace as T


def read(ctx):
    tr = ctx.get("trace")
    w = T.window_s(tr) if tr else 0.0
    return 100.0 * (1.0 - T.busy_s(tr) / w) if w > 0 else None
