"""Whole planning pass: weights planned per second times the least bytes
any planner moves per weight -- read the float32 weight (4 bytes), write
its stored bits ((cols + 1) / 8 bytes: magnitude columns and sign) --
against the chip's HBM bandwidth.  Planning is bound by memory, so this is
the share of the bound that binds; it bounds a pricing kernel's claim."""


def read(ctx):
    win = ctx["window"]
    cols = ctx["traffic"]["planner"]["cols"]
    per_s = win["weights"] / win["end"]
    return 100.0 * per_s * (4.0 + (cols + 1) / 8.0) / ctx["peaks"]["hbm_bytes_per_s"]
