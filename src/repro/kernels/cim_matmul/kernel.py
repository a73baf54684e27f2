"""Pallas TPU kernels for the bit-sliced CIM matmul.

TPU co-design (DESIGN.md §2): a naive bit-sliced matmul issues one matmul
per bit column and re-reads the activation tile ``cols`` times from HBM.
These kernels keep the activation tile resident in VMEM across all planes
and offer three execution modes:

  * ``fused_dequant`` (int8 planes, parity oracle): reconstruct the weight
    tile in VMEM with a VPU weighted-sum over planes (w = sum_b 2^b * P_b),
    then one MXU matmul per (bm, bn, bk) tile.  MXU work equals a dense
    matmul; the bit-plane storage cost is paid only in HBM->VMEM bytes.
  * ``planes`` (int8 planes, faithful crossbar dataflow): one MXU matmul per
    plane with power-of-two scaling on the partial sums — mirrors how the
    analog array accumulates per-column dot products, useful for studying
    per-column error injection at matmul time.
  * **packed** (``cim_matmul_packed_kernel``, the serving hot path): the
    weight operand arrives bit-packed — ``uint8[cols, K/8, N]`` planes plus a
    ``uint8[K/8, N]`` sign-bit mask — so each stored bit cell costs exactly
    one bit of HBM traffic ((cols+1)/8 bytes per weight vs ``cols`` bytes for
    the int8-plane operand, an ~8x reduction).  Bits are unpacked in VMEM
    with shift/mask on the VPU, signs applied digitally, then one MXU dot.

Int8-plane grid: (M/bm, N/bn, K/bk), K innermost so the f32 accumulator tile
lives in a VMEM scratch across the K loop.  Packed grid: (N/bn, K/bk) with
the *whole* (padded) M resident in VMEM — decode-time M is tiny (batch x 1),
and hoisting the M axis out of the grid means each weight tile is unpacked
exactly once per (j, kk), never redone per M block (the ops wrapper chunks
very large M at the JAX level instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import cdiv


def _kernel(x_ref, p_ref, o_ref, acc_ref, *, cols: int, n_k: int, mode: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    if mode == "fused_dequant":
        # VPU: reconstruct the quantized weight tile, then a single MXU dot.
        w = jnp.zeros(p_ref.shape[1:], dtype=jnp.float32)  # (bk, bn)
        for b in range(cols):
            w = w + (2.0**b) * p_ref[b, :, :].astype(jnp.float32)
        acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    elif mode == "planes":
        # Faithful per-column accumulation: one MXU dot per bit plane.
        partial = jnp.zeros(acc_ref.shape, dtype=jnp.float32)
        for b in range(cols):
            plane = p_ref[b, :, :].astype(jnp.float32)
            partial += (2.0**b) * jax.lax.dot(x, plane, preferred_element_type=jnp.float32)
        acc_ref[...] += partial
    else:
        raise ValueError(f"unknown mode {mode!r}")

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "mode", "interpret")
)
def cim_matmul_kernel(
    x: jax.Array,
    splanes: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    mode: str = "fused_dequant",
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel entry: shapes must already be padded to block multiples.

    x: f32[M, K]; splanes: int8[cols, K, N] -> f32[M, N] (unscaled).
    """
    m, k = x.shape
    cols, k2, n = splanes.shape
    assert k == k2, (k, k2)
    # block multiples are a hard precondition: a ragged tail block would read
    # out of bounds in interpret mode and miscompile on Mosaic
    assert m % bm == 0, f"M={m} not a multiple of bm={bm}"
    assert n % bn == 0, f"N={n} not a multiple of bn={bn}"
    assert k % bk == 0, f"K={k} not a multiple of bk={bk}"
    n_k = cdiv(k, bk)
    grid = (cdiv(m, bm), cdiv(n, bn), n_k)

    return pl.pallas_call(
        functools.partial(_kernel, cols=cols, n_k=n_k, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((cols, bk, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="cim_matmul_kernel",
    )(x, splanes)


# ---------------------------------------------------------------------------
# Packed-plane mode (serving hot path)
# ---------------------------------------------------------------------------

def _unpack_bits(bytes_2d: jax.Array, bk: int, bn: int) -> jax.Array:
    """uint8/int32[bk/8, bn] byte block -> int32[bk, bn] bits in {0, 1}.

    Row ``r`` of the output is bit ``7 - (r % 8)`` of byte ``r // 8`` — the
    MSB-first convention of ``jnp.packbits`` / ``bitslice.pack_linear_planes``.
    Written with repeat + broadcasted_iota (no sublane reshape) so it lowers
    on both Mosaic and the interpreter.
    """
    rep = jnp.repeat(bytes_2d.astype(jnp.int32), 8, axis=0)  # (bk, bn)
    shifts = 7 - jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0) % 8
    return (rep >> shifts) & 1


def _packed_kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, *, cols: int, n_k: int):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk8, bn = s_ref.shape
    bk = bk8 * 8
    # VPU: unpack the bit planes into a magnitude tile, apply signs digitally.
    # This runs once per (j, kk) — the M axis lives inside the single MXU dot
    # below, so reconstruction is never redone per M block.
    w = jnp.zeros((bk, bn), dtype=jnp.float32)
    for b in range(cols):
        w = w + (2.0**b) * _unpack_bits(p_ref[b, :, :], bk, bn).astype(jnp.float32)
    sgn = 1.0 - 2.0 * _unpack_bits(s_ref[...], bk, bn).astype(jnp.float32)
    w = w * sgn
    x = x_ref[...].astype(jnp.float32)  # (M, bk)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _packed_skip_kernel(
    nz_ref, x_ref, p_ref, s_ref, o_ref, acc_ref, w_ref, *, cols: int, n_k: int
):
    """Packed kernel twin with zero-tile skipping (const_rle serving codec).

    ``nz_ref`` (SMEM, scalar-prefetched) holds one flag per (plane, K-block)
    tile, flattened row-major to int32[cols * n_k]; a 0 flag means every byte
    of that plane's K-block is zero across all N, so its unpack+accumulate is
    skipped.  Bit-exact with ``_packed_kernel``: a skipped tile contributes
    exact zeros to the magnitude tile.  The reconstruction accumulates in a
    VMEM scratch (``w_ref``) because ``pl.when`` bodies mutate refs, not
    loop-carried values.
    """
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk8, bn = s_ref.shape
    bk = bk8 * 8
    w_ref[...] = jnp.zeros_like(w_ref)
    for b in range(cols):
        @pl.when(nz_ref[b * n_k + kk] != 0)
        def _acc(b=b):
            w_ref[...] += (2.0**b) * _unpack_bits(p_ref[b, :, :], bk, bn).astype(
                jnp.float32
            )
    sgn = 1.0 - 2.0 * _unpack_bits(s_ref[...], bk, bn).astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)  # (M, bk)
    acc_ref[...] += jax.lax.dot(x, w_ref[...] * sgn, preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def cim_matmul_packed_skip_kernel(
    x: jax.Array,
    planes_packed: jax.Array,
    sign_packed: jax.Array,
    tile_nz: jax.Array,
    *,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Raw packed entry with zero-tile skip flags (same contract as
    :func:`cim_matmul_packed_kernel`, plus ``tile_nz`` int32[cols * K/bk]
    flattened row-major from uint8[cols, K/bk] — see
    ``core.planes.encode_operands``).  Flags ride the scalar-prefetch lane
    (SMEM), so the skip predicates are known before each grid step runs."""
    m, k = x.shape
    cols, kw, n = planes_packed.shape
    assert bk % 8 == 0, f"bk={bk} must be a multiple of 8 (packed K bytes)"
    assert kw * 8 == k, f"planes K/8={kw} inconsistent with x K={k}"
    assert sign_packed.shape == (kw, n), (sign_packed.shape, (kw, n))
    assert m % 8 == 0, f"M={m} not a multiple of 8"
    assert n % bn == 0, f"N={n} not a multiple of bn={bn}"
    assert k % bk == 0, f"K={k} not a multiple of bk={bk}"
    n_k = cdiv(k, bk)
    assert tile_nz.shape == (cols * n_k,), (tile_nz.shape, cols, n_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cdiv(n, bn), n_k),
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, kk, nz: (0, kk)),
            pl.BlockSpec((cols, bk // 8, bn), lambda j, kk, nz: (0, kk, j)),
            pl.BlockSpec((bk // 8, bn), lambda j, kk, nz: (kk, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, kk, nz: (0, j)),
        scratch_shapes=[
            pltpu.VMEM((m, bn), jnp.float32),
            pltpu.VMEM((bk, bn), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_packed_skip_kernel, cols=cols, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="cim_matmul_packed_skip_kernel",
    )(tile_nz.astype(jnp.int32), x, planes_packed, sign_packed)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def cim_matmul_packed_kernel(
    x: jax.Array,
    planes_packed: jax.Array,
    sign_packed: jax.Array,
    *,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Raw packed-mode entry: shapes must already be padded to block multiples.

    x: f32[M, K]; planes_packed: uint8[cols, K/8, N] (plane 0 = LSB, K packed
    MSB-first per byte); sign_packed: uint8[K/8, N] (bit 1 = negative).
    Returns f32[M, N] (unscaled).  Grid is (N/bn, K/bk) with all of M
    resident in VMEM — callers chunk M before invoking (see ops.py).
    """
    m, k = x.shape
    cols, kw, n = planes_packed.shape
    assert bk % 8 == 0, f"bk={bk} must be a multiple of 8 (packed K bytes)"
    assert kw * 8 == k, f"planes K/8={kw} inconsistent with x K={k}"
    assert sign_packed.shape == (kw, n), (sign_packed.shape, (kw, n))
    assert m % 8 == 0, f"M={m} not a multiple of 8"
    assert n % bn == 0, f"N={n} not a multiple of bn={bn}"
    assert k % bk == 0, f"K={k} not a multiple of bk={bk}"
    n_k = cdiv(k, bk)
    grid = (cdiv(n, bn), n_k)

    return pl.pallas_call(
        functools.partial(_packed_kernel, cols=cols, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((cols, bk // 8, bn), lambda j, kk: (0, kk, j)),
            pl.BlockSpec((bk // 8, bn), lambda j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        interpret=interpret,
        name="cim_matmul_packed_kernel",
    )(x, planes_packed, sign_packed)
