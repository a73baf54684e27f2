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
    the int8-plane operand, an ~8x reduction).  Each grid step decodes a
    tile of up to 2048 x 512 weights (``ops.packed_tiles`` sizes it from the
    shapes) in chunks of 256 K rows across the tile's width, and feeds each
    chunk to the MXU as it is decoded.  A chunk's bytes are read
    as 32-bit words of four K bytes each; eight planes at a time go through
    a bit-matrix transpose (delta swaps, one op for four bytes), which
    leaves in each byte the magnitude bits of one weight.  Planes 0-7 give
    an exact 8-bit integer, planes 8-15 a second one, and the sign bit is
    set on their float values.  bfloat16 activations meet these parts in
    bfloat16 MXU dots (``x @ lo + 256 * (x @ hi)``, exact products, float32
    sums); float32 activations meet one float32 tile of the exact weights.

Int8-plane grid: (M/bm, N/bn, K/bk), K innermost so the f32 accumulator tile
lives in a VMEM scratch across the K loop.  Packed grid: (N/bn, K/bk) with
the *whole* (padded) M resident in VMEM — decode-time M is tiny (batch x 1),
and hoisting the M axis out of the grid means each weight tile is decoded
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

# A chunk is 32 K bytes of every plane across the whole N block, viewed as
# 8 rows of 32-bit words of four K bytes each.  The block's full width (up
# to 512 lanes) gives each traced op several independent vector registers,
# which keeps the VPU's slots busy across load and MXU latencies without
# unrolling the loop (an unrolled body multiplies the traced ops, and so
# the time to lower the kernel in every program that calls it).
CHUNK_BYTES = 32
LANES = 128
# K rows of one chunk; the K block ``bk`` is a multiple of it.
K_UNIT = 8 * CHUNK_BYTES

# (distance, mask) of the three delta swaps that transpose 8x8 bit blocks
_SWAPS = ((4, 0x0F0F0F0F), (2, 0x33333333), (1, 0x55555555))


def _transpose8(rows: list) -> list:
    """Transpose the 8x8 bit matrix held in each byte lane of 8 words.

    ``rows[b]`` is a uint32 word array (``None`` stands for all zeros); in
    the result, bit ``b`` of each byte of word ``c`` is bit ``c`` of the
    same byte of ``rows[b]``.  Three rounds of delta swaps, each op serving
    the four bytes of a word at once; absent rows drop the ops they would
    only feed zeros.
    """
    a = list(rows) + [None] * (8 - len(rows))
    for s, mask in _SWAPS:
        m = jnp.uint32(mask)
        for b in range(8):
            if b & s:
                continue
            lo, hi = a[b], a[b + s]
            if lo is None and hi is None:
                continue
            if hi is None:
                a[b], a[b + s] = lo & m, (lo >> s) & m
            elif lo is None:
                a[b], a[b + s] = (hi & m) << s, hi & ~m
            else:
                t = ((lo >> s) ^ hi) & m
                a[b], a[b + s] = lo ^ (t << s), hi ^ t
    return a


def _words(block: jax.Array) -> jax.Array:
    """uint8[32, W] -> uint32[8, W]: byte ``p`` of word ``i`` is row ``4i + p``."""
    return pltpu.bitcast(block, jnp.uint32)


def _by_byte(w: jax.Array, left: bool) -> jax.Array:
    """uint32[8, W] -> uint32[4, 8, W]: at ``p``, byte ``p`` of ``w`` moved to
    the low byte (``left=False``) or to the top byte (``left=True``)."""
    w = jnp.broadcast_to(w, (4,) + w.shape)
    p8 = jax.lax.broadcasted_iota(jnp.uint32, w.shape, 0) * 8
    return w << (24 - p8) if left else w >> p8


def _chunk(x_ref, p_ref, s_ref, acc_ref, rc, *, cols: int, live: int):
    """Decode one chunk of every plane and add its product to ``acc_ref``.

    The chunk is K bytes ``32 rc + 4i + p`` (word ``i``, byte ``p``) by the
    N block.  Bit ``7 - j`` of byte ``32 rc + 4i + p`` is K row
    ``8 (32 rc + 4i + p) + j``; its decoded weight goes to row ``32j + 8p +
    i`` of the chunk's 256-row tile, and the wrapper orders the activation
    columns to match.  Planes ``8g .. 8g + 7`` give the integer ``g``;
    groups from ``live`` on are known to be zero here and are skipped.
    float32 activations meet one tile of the exact weights; bfloat16 ones
    one tile per group, each an exact 8-bit integer with the sign.
    """
    rows = pl.ds(pl.multiple_of(rc * CHUNK_BYTES, CHUNK_BYTES), CHUNK_BYTES)
    sign = _by_byte(_words(s_ref[rows, :]), left=True)
    groups = [
        _transpose8([
            _words(p_ref[b, rows, :]) for b in range(8 * g, min(cols, 8 * g + 8))
        ])
        for g in range(live)
    ]
    combined = x_ref.dtype == jnp.float32
    y = None
    for h in range(2):  # two 128-row halves of the tile
        parts = [[] for _ in range(1 if combined else live)]
        for j in range(4 * h, 4 * h + 4):
            sign_bit = (sign << j) & jnp.uint32(0x80000000)  # [p]: bit 7 - j of byte p
            mags = [_by_byte(words[7 - j], left=False) & 0xFF for words in groups]
            if combined:
                mag = mags[0]
                for g in range(1, live):
                    mag = mag | (mags[g] << (8 * g))
                mags = [mag]
            for g, mag in enumerate(mags):
                f = pltpu.bitcast(pltpu.bitcast(mag, jnp.int32).astype(jnp.float32), jnp.uint32)
                parts[g].append(pltpu.bitcast(f ^ sign_bit, jnp.float32).reshape(32, -1))
        cols_h = pl.ds(pl.multiple_of(rc * K_UNIT + 128 * h, 128), 128)
        x = x_ref[:, cols_h]
        # bfloat16 parts are exact: one MXU pass, whatever precision is asked
        precision = None if combined else jax.lax.Precision.DEFAULT
        for g, vals in enumerate(parts):
            w = jnp.concatenate(vals, axis=0).astype(x.dtype)  # (128, W)
            d = jax.lax.dot(x, w, precision=precision, preferred_element_type=jnp.float32)
            d = d * 256.0**g if g else d
            y = d if y is None else y + d
    acc_ref[...] += y


def _packed_kernel(*refs, cols: int, n_k: int, skip: bool):
    """One (N block, K block) step: decode the tile chunk by chunk in VMEM
    and feed each chunk to the MXU as it is decoded.

    With ``skip``, a scalar-prefetched count per chunk says how many
    8-plane groups hold a nonzero byte there; the rest are not decoded and
    add nothing, which leaves the result as the plain kernel has it (up to
    the sign of a zero).
    """
    if skip:
        live_ref, x_ref, p_ref, s_ref, o_ref, acc_ref = refs
    else:
        x_ref, p_ref, s_ref, o_ref, acc_ref = refs
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_rows = s_ref.shape[0] // CHUNK_BYTES
    n_groups = cdiv(cols, 8)

    def step(rc, carry):
        chunk = functools.partial(_chunk, x_ref, p_ref, s_ref, acc_ref, rc, cols=cols)
        if skip:
            live = live_ref[kk * n_rows + rc]
            for n_live in range(1, n_groups + 1):
                pl.when(live == n_live)(functools.partial(chunk, live=n_live))
        else:
            chunk(live=n_groups)
        return carry

    jax.lax.fori_loop(0, n_rows, step, 0)

    @pl.when(kk == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _packed_call(name, x, planes_packed, sign_packed, live, *, bn, bk, interpret):
    m, k = x.shape
    cols, kw, n = planes_packed.shape
    assert x.dtype in (jnp.bfloat16, jnp.float32), x.dtype
    assert bk % K_UNIT == 0, f"bk={bk} must be a multiple of {K_UNIT}"
    assert bn % LANES == 0, f"bn={bn} must be a multiple of {LANES}"
    assert kw * 8 == k, f"planes K/8={kw} inconsistent with x K={k}"
    assert sign_packed.shape == (kw, n), (sign_packed.shape, (kw, n))
    assert m % 8 == 0, f"M={m} not a multiple of 8"
    assert n % bn == 0, f"N={n} not a multiple of bn={bn}"
    assert k % bk == 0, f"K={k} not a multiple of bk={bk}"
    n_k = k // bk
    skip = live is not None

    def idx(f):
        return (lambda j, kk, *_: f(j, kk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(skip),
        grid=(n // bn, n_k),
        in_specs=[
            pl.BlockSpec((m, bk), idx(lambda j, kk: (0, kk))),
            pl.BlockSpec((cols, bk // 8, bn), idx(lambda j, kk: (0, kk, j))),
            pl.BlockSpec((bk // 8, bn), idx(lambda j, kk: (kk, j))),
        ],
        out_specs=pl.BlockSpec((m, bn), idx(lambda j, kk: (0, j))),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
    )
    args = (live,) if skip else ()
    return pl.pallas_call(
        functools.partial(_packed_kernel, cols=cols, n_k=n_k, skip=skip),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name=name,
    )(*args, x, planes_packed, sign_packed)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def cim_matmul_packed_kernel(
    x: jax.Array,
    planes_packed: jax.Array,
    sign_packed: jax.Array,
    *,
    bn: int = 512,
    bk: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Raw packed-mode entry: shapes must already be padded to block multiples.

    x: bf16/f32[M, K] with each 256-column run in the decoded tile's row
    order (``ops.cim_matmul_packed`` orders them); planes_packed: uint8[cols, K/8,
    N] (plane 0 = LSB, K packed MSB-first per byte); sign_packed:
    uint8[K/8, N] (bit 1 = negative).  Returns f32[M, N] (unscaled).  Grid
    is (N/bn, K/bk) with all of M resident in VMEM — callers chunk M before
    invoking (see ops.py), and ``ops.packed_tiles`` picks bk and bn from
    the shapes.  Each step decodes its (bk, bn) tile 256 K rows at a time
    with byte-parallel bit transposes (``_chunk``) and feeds each chunk to
    the MXU as it is decoded.
    """
    return _packed_call(
        "cim_matmul_packed_kernel", x, planes_packed, sign_packed, None,
        bn=bn, bk=bk, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def cim_matmul_packed_skip_kernel(
    x: jax.Array,
    planes_packed: jax.Array,
    sign_packed: jax.Array,
    live_groups: jax.Array,
    *,
    bn: int = 512,
    bk: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Raw packed entry with zero-chunk skipping (the const_rle serving codec).

    Same contract as :func:`cim_matmul_packed_kernel`, plus ``live_groups``
    int32[K/256]: for each run of 32 K bytes, one more than the highest
    8-plane group with a nonzero byte there (0: all planes zero).  The
    groups from it on are not decoded.  The counts ride the scalar-prefetch
    lane (SMEM), so each is known before its grid step runs.
    """
    assert live_groups.shape == (x.shape[1] // K_UNIT,), (live_groups.shape, x.shape)
    return _packed_call(
        "cim_matmul_packed_skip_kernel", x, planes_packed, sign_packed,
        live_groups.astype(jnp.int32), bn=bn, bk=bk, interpret=interpret,
    )
