"""Paged KV view: device time of the ops under the ``kv_gather`` and
``kv_scatter`` scopes (``models/blocks.gather_pool_view`` and
``scatter_pool_view``) inside the decode program, per decode dispatch, in
the traced window; XLA's copies count under the scope of the value they
copy (``spans.load``).  Dispatches are counted as runs of the decode program
on the device (``XLA Modules`` events), each weighted by the share of it
inside the window.  The same per dispatch for the ``attention`` and
``sample`` scopes, and for the decode program's other copies, is printed on
stderr."""
from bench import common, spans as S

DECODE = "decode_loop"
KV = ("kv_gather", "kv_scatter")


def _ms(sp, lo, hi, runs, pick):
    sec = S.device_seconds(sp, lo, hi, lambda names, module, name: DECODE in module and pick(
        names, name))
    return None if sec is None else 1e3 * sec / runs


def read(ctx):
    sp = S.get(ctx)
    if sp is None:
        return None
    lo, hi = ctx["trace"]["window"]
    runs = S.module_runs(sp, lo, hi, DECODE)
    if not runs:
        return None
    parts = {s: _ms(sp, lo, hi, runs, lambda names, _, s=s: s in names)
             for s in (*KV, "attention", "sample")}
    parts["other copies"] = _ms(sp, lo, hi, runs, lambda names, name: name.startswith(
        "copy") and not set(KV) & set(names))
    common.log(f"kv_view_ms: over {runs:.3f} decode dispatches, device ms each: " + ", ".join(
        f"{s} {v:.6f}" for s, v in parts.items() if v is not None))
    return _ms(sp, lo, hi, runs, lambda names, _: any(s in names for s in KV))
