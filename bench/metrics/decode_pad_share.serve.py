"""Engine scheduler: the share of decode rows a dispatch pads to its bucket.
Sum of ``rows_padded`` over sum of ``rows + rows_padded`` over the decode
dispatches (``engine.decode`` spans, and the decode sub-batch of
``engine.fused`` ones) begun in the traced window: rows the chip computes and
the engine throws away.

On stderr, the shapes of the window's dispatches from the same counters:
for decode the mean ``rows``, ``pages`` and quantum ``q``; for prefill
(``engine.prefill``, and the prefill sub-batch of ``engine.fused``) its own
pad share, mean ``pages`` and prompt ``tokens`` a dispatch."""
from bench import common, spans as S

# counters of a dispatch's decode and prefill sub-batches, by span
DECODE = {"engine.decode": ("rows", "rows_padded"), "engine.fused": ("rows", "rows_padded")}
PREFILL = {"engine.prefill": ("rows", "rows_padded"),
           "engine.fused": ("prefill_rows", "prefill_rows_padded")}


def shapes(sp: dict, lo: int, hi: int, kinds: dict) -> dict | None:
    """Totals over the dispatches of ``kinds`` begun in [lo, hi): their
    number, live and padded rows, and the sums of ``pages``, ``q`` and
    ``tokens``; None without one."""
    tot = dict.fromkeys(("n", "rows", "pad", "pages", "q", "tokens"), 0)
    for s in S.named(sp, *kinds):
        rows, pad = kinds[s[0]]
        if lo <= s[1] < hi and s[4].get(rows):
            tot["n"] += 1
            tot["rows"] += s[4][rows]
            tot["pad"] += s[4].get(pad, 0)
            for k in ("pages", "q", "tokens"):
                tot[k] += s[4].get(k, 0)
    return tot if tot["n"] else None


def read(ctx):
    sp = S.get(ctx)
    if sp is None:
        return None
    lo, hi = ctx["trace"]["window"]
    dec, pre = shapes(sp, lo, hi, DECODE), shapes(sp, lo, hi, PREFILL)
    if pre:
        common.log(f"decode_pad_share: {pre['n']} prefill dispatches, pad share "
                   f"{100.0 * pre['pad'] / (pre['rows'] + pre['pad']):.3f}%, a dispatch "
                   f"{pre['rows'] / pre['n']:.3f} rows, {pre['pages'] / pre['n']:.3f} pages, "
                   f"{pre['tokens'] / pre['n']:.3f} tokens")
    if not dec:
        return None
    common.log(f"decode_pad_share: {dec['n']} decode dispatches, a dispatch "
               f"{dec['rows'] / dec['n']:.3f} rows, {dec['pad'] / dec['n']:.3f} padded, "
               f"{dec['pages'] / dec['n']:.3f} pages, quantum {dec['q'] / dec['n']:.3f}")
    return 100.0 * dec["pad"] / (dec["rows"] + dec["pad"])
