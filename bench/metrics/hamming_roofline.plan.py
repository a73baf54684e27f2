"""Pricing kernel: the least time the chip could take for every Pallas
``hamming`` call (``price_pairs``) in the traced window
(``bench/roofline/hamming.py`` at each call's shapes, read from the trace)
over the time the calls took."""
from bench import common, trace as T

rl = common.load_module("roofline", "hamming")


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    spent = least = 0.0
    for sec, detail in T.kernel_calls(tr, "hamming"):
        call = rl.parse_call(detail)
        if call is None:
            return None
        spent += sec
        least += rl.least_seconds(*call, ctx["peaks"])[0]
    if spent <= 0:
        return None
    common.log(f"hamming: {spent:.6f} s in the traced window; least {least:.6f} s")
    return 100.0 * least / spent
