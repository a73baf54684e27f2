"""Public jit'd wrappers for the CIM matmul kernels (pad, dispatch, scale)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._util import cdiv, default_interpret, pad_axis_to, round_up
from repro.kernels.cim_matmul.kernel import (
    K_UNIT,
    LANES,
    cim_matmul_kernel,
    cim_matmul_packed_kernel,
    cim_matmul_packed_skip_kernel,
)


def _block(requested: int, dim: int, unit: int) -> int:
    """Clamp a requested block size to the problem while keeping it a multiple
    of the hardware ``unit``.

    The naive ``min(b, round_up(dim, unit))`` can return a non-multiple of
    ``unit`` when the caller's ``b`` isn't one (and the padded dim, a multiple
    of the *block*, is then not tile-aligned) — degenerate decode shapes
    (M = 1..8) hit exactly this.  Rounding the clamp itself keeps every padded
    axis a multiple of both the block and the unit; the kernel entries assert
    the invariant.
    """
    return round_up(min(requested, round_up(dim, unit)), unit)


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk", "interpret"))
def cim_matmul(
    x: jax.Array,
    splanes: jax.Array,
    scale: jax.Array | float = 1.0,
    *,
    mode: str = "fused_dequant",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """y = scale * sum_b 2^b * (x @ splanes[b]) — see ref.py for the contract.

    Accepts arbitrary (M, K, N); pads to MXU-aligned block multiples and
    slices the result back.  ``interpret=None`` auto-selects: compiled on
    TPU, interpreted elsewhere (this container).
    """
    m, k = x.shape
    cols, k2, n = splanes.shape
    if k != k2:
        raise ValueError(f"K mismatch: x has {k}, splanes has {k2}")
    interp = default_interpret(interpret)

    bm_ = _block(bm, m, 8)
    bn_ = _block(bn, n, 128)
    bk_ = _block(bk, k, 128)
    xp = pad_axis_to(pad_axis_to(x, 0, round_up(m, bm_)), 1, round_up(k, bk_))
    pp = pad_axis_to(pad_axis_to(splanes, 1, round_up(k, bk_)), 2, round_up(n, bn_))

    y = cim_matmul_kernel(xp, pp, bm=bm_, bn=bn_, bk=bk_, mode=mode, interpret=interp)
    return y[:m, :n] * jnp.asarray(scale, dtype=jnp.float32)


# Largest tile: 2048 K rows x 512 N columns, about 60 grid steps per
# transformer layer at decode, each moving 1.4 MB of planes.
_MAX_K_UNITS = 2048 // K_UNIT
_MAX_N_LANES = 512 // LANES
_VMEM_BUDGET = 12 * 2**20  # of v5e's 16 MiB scoped VMEM


def _largest_divisor(n: int, limit: int) -> int:
    return max(d for d in range(1, min(n, limit) + 1) if n % d == 0)


def _vmem_bytes(m: int, bk: int, bn: int, cols: int, x_bytes: int) -> int:
    """VMEM of one grid step: double-buffered blocks and the accumulator."""
    blocks = m * bk * x_bytes + (cols + 1) * (bk // 8) * bn + m * bn * 4
    return 2 * blocks + m * bn * 4


def packed_tiles(m: int, k: int, n: int, cols: int, x_bytes: int) -> tuple[int, int]:
    """(bk, bn) for an (M, K, N) packed matmul, from the shapes alone.

    bn: the largest multiple of 128 up to 512 that divides N rounded up to
    128; bk: the largest multiple of 256 up to 2048 that divides K rounded
    up to 256 and keeps one grid step inside the VMEM budget.  Dividing
    the rounded shapes means the packed planes are padded only where K or
    N is not a multiple of 256 or 128 — never at the widths models use.
    """
    n_lanes, k_units = cdiv(n, LANES), cdiv(k, K_UNIT)
    bn = LANES * _largest_divisor(n_lanes, _MAX_N_LANES)
    fits = [
        d for d in range(1, min(k_units, _MAX_K_UNITS) + 1)
        if k_units % d == 0
        and _vmem_bytes(m, d * K_UNIT, bn, cols, x_bytes) <= _VMEM_BUDGET
    ]
    return K_UNIT * max(fits, default=1), bn


def _tile_order(x: jax.Array) -> jax.Array:
    """Order the activation columns as the kernel decodes weight rows.

    Within each run of 256 columns, K row ``32i + 8p + j`` (bit ``7 - j`` of
    byte ``4i + p``) goes to column ``32j + 8p + i``.
    """
    m, k = x.shape
    x = x.reshape(m, k // K_UNIT, 8, 4, 8)  # (M, run, i, p, j)
    return x.transpose(0, 1, 4, 3, 2).reshape(m, k)


def _live_groups(tile_nz: jax.Array, cols: int, kp: int) -> jax.Array:
    """Zero-tile flags uint8[cols, K/128] -> int32[K/256]: per 256 K rows,
    one more than the highest 8-plane group with a nonzero flag."""
    nz = pad_axis_to(tile_nz != 0, 1, kp // 128).reshape(cols, kp // K_UNIT, 2).any(-1)
    nz = pad_axis_to(nz, 0, 8 * cdiv(cols, 8)).reshape(-1, 8, kp // K_UNIT).any(1)
    rank = jnp.arange(1, nz.shape[0] + 1, dtype=jnp.int32)[:, None]
    return jnp.max(jnp.where(nz, rank, 0), axis=0)


@functools.partial(jax.jit, static_argnames=("m_chunk", "interpret"))
def cim_matmul_packed(
    x: jax.Array,
    planes_packed: jax.Array,
    sign_packed: jax.Array,
    scale: jax.Array | float = 1.0,
    *,
    m_chunk: int = 256,
    interpret: bool | None = None,
    tile_nz: jax.Array | None = None,
) -> jax.Array:
    """Bit-packed serving matmul: y = scale * (x @ unpack(planes, signs)).

    Operand contract (``bitslice.pack_linear_planes`` / ``pack_linear_sign``):
    planes_packed uint8[cols, ceil(K/8), N] with plane 0 = LSB and K packed
    MSB-first per byte; sign_packed uint8[ceil(K/8), N] with bit 1 = negative.
    Each stored bit cell costs one bit of HBM traffic — (cols+1)/8 bytes per
    weight vs ``cols`` bytes for the int8-plane operand.

    Arbitrary (M, K, N), K need not divide 8.  M is processed in chunks of
    ``m_chunk`` rows so the whole-M-resident kernel grid stays inside VMEM;
    within a chunk each weight tile is decoded once, never per M block.
    Tiles come from the shapes (:func:`packed_tiles`): up to 2048 x 512
    weights a grid step.  The kernel decodes a tile with byte-parallel
    integer ops on 32-bit words (bit-matrix transposes of 8 planes at a
    time) into exact integer parts, and this wrapper orders each run of 256
    activation columns to match the decoded rows.  bfloat16
    activations meet bfloat16 parts of at most 8 bits each
    (``x @ lo + 256 * (x @ hi)``, exact products, float32 sums); float32
    activations meet one float32 tile of the exact weights; other dtypes
    are taken as float32.

    ``tile_nz`` (uint8[cols, ceil(ceil(K/8)/16)] — the const_rle serving
    codec's zero-tile flags, ``core.planes.encode_operands``) routes to the
    skip-kernel twin, which leaves undecoded the 8-plane groups whose
    flags are all zero over a run of 256 K rows.  Equal to the flag-less
    path: both decode the same tile in the same order.
    """
    m, k = x.shape
    cols, kw, n = planes_packed.shape
    if kw != cdiv(k, 8):
        raise ValueError(f"planes K bytes {kw} != ceil({k}/8)")
    if sign_packed.shape != (kw, n):
        raise ValueError(f"sign shape {sign_packed.shape} != {(kw, n)}")
    interp = default_interpret(interpret)
    if x.dtype != jnp.bfloat16:
        x = x.astype(jnp.float32)

    mp = round_up(min(max(m, 1), m_chunk), 8)
    bk, bn = packed_tiles(mp, k, n, cols, x.dtype.itemsize)
    kp, np_ = round_up(k, bk), round_up(n, bn)
    xp = _tile_order(pad_axis_to(x, 1, kp))
    pp = pad_axis_to(pad_axis_to(planes_packed, 1, kp // 8), 2, np_)
    sp = pad_axis_to(pad_axis_to(sign_packed, 0, kp // 8), 1, np_)

    live = None
    if tile_nz is not None and tile_nz.shape == (cols, cdiv(kw, 16)):
        live = _live_groups(tile_nz, cols, kp)

    outs = []
    for m0 in range(0, max(m, 1), m_chunk):
        chunk = xp[m0 : m0 + m_chunk]
        xc = pad_axis_to(chunk, 0, round_up(chunk.shape[0], 8))
        if live is not None:
            yc = cim_matmul_packed_skip_kernel(
                xc, pp, sp, live, bn=bn, bk=bk, interpret=interp
            )
        else:
            yc = cim_matmul_packed_kernel(xc, pp, sp, bn=bn, bk=bk, interpret=interp)
        outs.append(yc[: chunk.shape[0]])
    y = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    return y[:m, :n] * jnp.asarray(scale, dtype=jnp.float32)
