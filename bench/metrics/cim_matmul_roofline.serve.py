"""Packed matmul kernel: the least time the chip could take for every
``cim_matmul_packed`` call in the traced window (``bench/roofline/cim_matmul.py``
at each call's own shapes, read from the trace) over the time the calls
took.  The bound that binds is printed on stderr."""
from bench import common, trace as T

rl = common.load_module("roofline", "cim_matmul")


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    spent = least = 0.0
    binds = {}
    for sec, detail in T.kernel_calls(tr, "cim_matmul_packed"):
        call = rl.parse_call(detail)
        if call is None:
            return None  # a call whose shapes cannot be read: no share
        t, bound = rl.least_seconds(*call, ctx["peaks"])
        spent += sec
        least += t
        binds[bound] = binds.get(bound, 0.0) + t
    if spent <= 0:
        return None
    common.log(f"cim_matmul_packed: {spent:.6f} s in the traced window; least "
               f"{least:.6f} s by bound {binds}")
    return 100.0 * least / spent
