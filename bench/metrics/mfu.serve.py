"""Whole serving step: model operations of every request the run served
(its prompt's prefill and each token decoded after the first, counted by
``bench/roofline/<name>.py`` for the reference the configuration names)
over the summed host spans of the dispatching steps that did the work --
the window's and the drain's -- against the chip's bfloat16 peak.  Time
the engine spends waiting for arrivals is left out, so a faster step
reads higher whatever the offered load; it bounds every kernel's share of
the step."""
from bench import common


def read(ctx):
    win, model = ctx["window"], ctx["model"]
    ops = common.load_module("roofline", ctx["config"]["reference"])
    busy = sum(b - a for a, b in win["steps"])
    total = 0.0
    for r in win["records"]:
        if not r["tokens"]:
            continue
        p = r["prompt"].size
        total += ops.prefill_ops(model, p)
        total += sum(ops.decode_ops(model, p + j) for j in range(1, len(r["tokens"])))
    if total <= 0 or busy <= 0:
        return None
    return 100.0 * total / busy / ctx["peaks"]["bf16_flops_per_s"]
