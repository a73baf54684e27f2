"""Operations and bytes of one pair-pricing call, ``price_pairs`` (Pallas ``hamming``).

For T pairs of packed crossbar sections (``uint8[T, W*C]`` each side) the
kernel returns ``int32[T]`` transition counts: ``popcount(a ^ b)`` summed over
each row.  The work is one XOR and one popcount-add per byte pair (2
integer operations per byte, against the int8 peak); the least traffic
reads both sides once and writes the counts once.  Pricing is bound by
memory at any size: 2 ops per 2 bytes read is far below the chip's
ops-to-bytes ratio.
"""
from __future__ import annotations

import re

_SHAPE = re.compile(r"(u8|s32)\[(\d+(?:,\d+)*)\]")


def ops_bytes(t: int, row_bytes: int) -> tuple[float, float]:
    ops = 2.0 * t * row_bytes
    nbytes = 2.0 * t * row_bytes + 4.0 * t
    return ops, nbytes


def parse_call(detail: str):
    """(T, row_bytes) from the HLO text of one kernel call, or None: the two
    ``u8[T, W*C]`` operands (padded to the kernel's blocks)."""
    operands = detail.split("(", 1)[1] if "(" in detail else detail  # past the output shape
    rows = [tuple(int(v) for v in s.split(",")) for t, s in _SHAPE.findall(operands) if t == "u8"]
    rows = [r for r in rows if len(r) == 2]
    if not rows:
        return None
    t, w = rows[0]
    return t, w


def least_seconds(t: int, row_bytes: int, peaks: dict) -> tuple[float, str]:
    ops, nbytes = ops_bytes(t, row_bytes)
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
