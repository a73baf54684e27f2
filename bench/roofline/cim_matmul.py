"""Operations and bytes of one packed crossbar matmul, ``cim_matmul_packed``.

``y[M, N] = x[M, K] @ unpack(planes uint8[cols, ceil(K/8), N], sign uint8[ceil(K/8), N])``:
the useful work is the ``M x K x N`` product (2 operations per
multiply-add); the in-kernel unpack of bit planes to bfloat16 is overhead,
not work.  The least traffic reads the activations once, every stored bit
once ((cols + 1) / 8 bytes per weight: ``benchmarks/roofline.cim_weight_bytes``
"packed") and writes the float32 output once.  The MXU computes in bfloat16,
so the bfloat16 peak bounds the operations.
"""
from __future__ import annotations

import re

_SHAPE = re.compile(r"(bf16|f32|u8|s8|f16)\[(\d+(?:,\d+)*)\]")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "u8": 1, "s8": 1}


def ops_bytes(m: int, k: int, n: int, cols: int, x_bytes: int = 2) -> tuple[float, float]:
    kw = -(-k // 8)
    ops = 2.0 * m * k * n
    nbytes = m * k * x_bytes + (cols + 1) * kw * n + m * n * 4
    return ops, float(nbytes)


def parse_call(detail: str):
    """(M, K, N, cols, x_bytes) from the HLO text of one kernel call, or None.

    The call's operands are ``x[M, K]``, ``planes u8[cols, K/8, N]`` and
    ``sign u8[K/8, N]`` (padded to the kernel's blocks)."""
    operands = detail.split("(", 1)[1] if "(" in detail else detail  # past the output shape
    shapes = [(t, tuple(int(v) for v in s.split(","))) for t, s in _SHAPE.findall(operands)]
    planes = [s for t, s in shapes if t == "u8" and len(s) == 3]
    xs = [(t, s) for t, s in shapes if t in ("bf16", "f32", "f16") and len(s) == 2]
    if not planes or not xs:
        return None
    cols, kw, n = planes[0]
    for t, (m, k) in xs:
        if -(-k // 8) == kw:
            return m, k, n, cols, _ITEM[t]
    return None


def least_seconds(m, k, n, cols, x_bytes, peaks: dict) -> tuple[float, str]:
    """The roofline time of one call and which bound binds."""
    ops, nbytes = ops_bytes(m, k, n, cols, x_bytes)
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
