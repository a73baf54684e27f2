"""Quantization and bit-plane slicing for bit-sliced CIM crossbars.

A bit-sliced crossbar of geometry ``rows x cols`` stores ``rows`` weights, one
per crossbar row, as ``cols``-bit unsigned magnitudes: column ``j`` is the
power-of-two multiplier ``2**j``.  Convention used throughout this package:

* plane axis is the **last** axis; index ``0`` is the **lowest-order column**
  (LSB) — the column the paper's bit-stucking targets.
* ``sign_magnitude`` encoding: ``w ~= sign * scale * q`` with ``q`` in
  ``[0, 2**cols - 1]``.  Signs are applied digitally (differential crossbar
  pairs); sorting by ``|w|`` therefore sorts the stored bit patterns, which is
  what Sorted Weight Sectioning exploits.
* ``offset_binary`` encoding (beyond-paper, §7 of DESIGN.md): ``w ~= scale * q
  + offset`` with all-positive ``q``.  The offset term is a rank-1 digital
  correction at matmul time: ``x @ W = scale * (x @ Q) + sum(x) * offset``.

All functions are pure JAX and jit-able.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

Encoding = Literal["sign_magnitude", "offset_binary"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Quantized:
    """A flat quantized tensor ready for sectioning.

    Attributes:
      q:      int32[n]  unsigned magnitudes in [0, 2**cols - 1].
      sign:   int8[n]   +1/-1 for sign_magnitude; all +1 for offset_binary.
      scale:  f32[]     dequantization scale.
      offset: f32[]     dequantization offset (0 for sign_magnitude).
      cols:   static    bitwidth.
      encoding: static  encoding name.
    """

    q: jax.Array
    sign: jax.Array
    scale: jax.Array
    offset: jax.Array
    cols: int
    encoding: str

    def tree_flatten(self):
        return (self.q, self.sign, self.scale, self.offset), (self.cols, self.encoding)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, sign, scale, offset = children
        cols, encoding = aux
        return cls(q=q, sign=sign, scale=scale, offset=offset, cols=cols, encoding=encoding)


def quantize(w: jax.Array, cols: int, encoding: Encoding = "sign_magnitude") -> Quantized:
    """Quantize a tensor (any shape; flattened) to ``cols``-bit crossbar form."""
    flat = jnp.ravel(w).astype(jnp.float32)
    levels = jnp.float32(2**cols - 1)
    # Explicit reciprocal multiply: XLA rewrites division-by-constant to a
    # reciprocal multiply in some compilation contexts but not others, which
    # would make eager and jitted quantization differ by 1 ULP in ``scale``.
    # A literal constant multiply is bit-deterministic everywhere, keeping
    # the planner's packed (jitted) and bool (eager) paths bit-identical.
    inv_levels = jnp.float32(1.0 / (2**cols - 1))
    if encoding == "sign_magnitude":
        amax = jnp.maximum(jnp.max(jnp.abs(flat)), jnp.finfo(jnp.float32).tiny)
        scale = amax * inv_levels
        q = jnp.clip(jnp.round(jnp.abs(flat) / scale), 0, levels).astype(jnp.int32)
        sign = jnp.where(flat < 0, -1, 1).astype(jnp.int8)
        offset = jnp.float32(0.0)
    elif encoding == "offset_binary":
        lo, hi = jnp.min(flat), jnp.max(flat)
        rng = jnp.maximum(hi - lo, jnp.finfo(jnp.float32).tiny)
        scale = rng * inv_levels
        q = jnp.clip(jnp.round((flat - lo) / scale), 0, levels).astype(jnp.int32)
        sign = jnp.ones_like(q, dtype=jnp.int8)
        offset = lo
    else:
        raise ValueError(f"unknown encoding: {encoding!r}")
    return Quantized(q=q, sign=sign, scale=scale, offset=offset, cols=cols, encoding=encoding)


def dequantize(qt: Quantized) -> jax.Array:
    """Inverse of :func:`quantize` (returns the flat tensor)."""
    mag = qt.q.astype(jnp.float32) * qt.scale
    if qt.encoding == "sign_magnitude":
        return mag * qt.sign.astype(jnp.float32)
    return mag + qt.offset


def dequantize_from_planes(
    planes: jax.Array, sign: jax.Array, scale: jax.Array, offset: jax.Array
) -> jax.Array:
    """Reassemble weights from (possibly error-injected) bit planes.

    planes: bool/int[..., cols] with plane 0 = LSB.  Returns f32[...].

    NOTE: the float result is only bit-reproducible *per compiled context* —
    XLA may contract the multiply chain with the offset add into an FMA, and
    whether it does depends on the surrounding fusion, so eager calls and
    differently-fused jits can disagree in the last ULP.  Callers needing
    bit-identical floats across call sites must route every call through ONE
    shared jitted entry (see ``planner._dequant_slots``, used by both
    planner impls) instead of inlining this into larger jits.
    """
    cols = planes.shape[-1]
    weights_of_two = (2 ** jnp.arange(cols, dtype=jnp.int32)).astype(jnp.int32)
    q = jnp.sum(planes.astype(jnp.int32) * weights_of_two, axis=-1)
    return q.astype(jnp.float32) * scale * sign.astype(jnp.float32) + offset


@partial(jax.jit, static_argnames=("cols",))
def bitplanes(q: jax.Array, cols: int) -> jax.Array:
    """Extract bit planes: int[...,] -> bool[..., cols]; plane 0 = LSB."""
    shifts = jnp.arange(cols, dtype=q.dtype)
    return ((q[..., None] >> shifts) & 1).astype(jnp.bool_)


def pack_rows(planes: jax.Array) -> jax.Array:
    """Pack the rows axis of bool[S, rows, cols] into uint8 words.

    Returns uint8[S, ceil(rows/8), cols].  Used for XOR+popcount transition
    counting (8x less data movement than bool planes).
    """
    s, rows, cols = planes.shape
    pad = (-rows) % 8
    if pad:
        planes = jnp.pad(planes, ((0, 0), (0, pad), (0, 0)))
    # jnp.packbits packs along the chosen axis, MSB-first within a byte.
    return jnp.packbits(planes.astype(jnp.uint8), axis=1)


def unpack_rows(packed: jax.Array, rows: int) -> jax.Array:
    """Inverse of :func:`pack_rows` -> bool[S, rows, cols]."""
    planes = jnp.unpackbits(packed, axis=1, count=rows)
    return planes.astype(jnp.bool_)


def pack_axis0(mask: jax.Array) -> jax.Array:
    """Pack axis 0 of bool[rows, k] into uint8[ceil(rows/8), k] words.

    Same MSB-first byte convention as :func:`pack_rows`; used to apply
    per-row Bernoulli masks directly to packed planes (bit stucking).
    """
    rows = mask.shape[0]
    pad = (-rows) % 8
    if pad:
        mask = jnp.pad(mask, ((0, pad),) + ((0, 0),) * (mask.ndim - 1))
    return jnp.packbits(mask.astype(jnp.uint8), axis=0)


def section_planes_packed(q: jax.Array, rows: int, cols: int) -> jax.Array:
    """int32[S*rows] magnitudes -> packed uint8[S, ceil(rows/8), cols] planes.

    The canonical planner representation: one packing pass per tensor, after
    which all pricing (cost/schedule/stucking) runs on packed words.
    ``q`` must already be padded to a multiple of ``rows``.

    Byte-identical to ``pack_rows(bitplanes(q.reshape(-1, rows), cols))``,
    but computed on the transposed magnitudes ``[W, 8, S]``, whose long
    section axis is minor: one reduction over the 8 rows of each byte builds
    ``[W, cols, S]`` bytes, and one transpose returns the canonical layout.
    The bool ``[S, rows, cols]`` planes and ``packbits``' ``[S, W, 8]``
    regrouping would otherwise be materialized on TPU with their small minor
    dims padded to full tiles (16x the magnitudes' bytes), which does not
    fit one chip's memory for a full-width LM tensor.
    """
    qt = q.reshape(-1, rows).T
    pad = (-rows) % 8
    if pad:
        qt = jnp.pad(qt, ((0, pad), (0, 0)))
    qt = qt.reshape(-1, 8, qt.shape[1])  # [W, 8, S]: row 8w+j
    col = jnp.arange(cols, dtype=qt.dtype)[None, None, :, None]
    msb_first = (7 - jnp.arange(8, dtype=qt.dtype))[None, :, None, None]
    bits = ((qt[:, :, None, :] >> col) & 1) << msb_first  # [W, 8, cols, S]
    word = jnp.sum(bits.astype(jnp.uint8), axis=1, dtype=jnp.uint8)
    return jnp.transpose(word, (2, 0, 1))


@partial(jax.jit, static_argnames=("cols",))
def pack_linear_planes(q: jax.Array, cols: int) -> jax.Array:
    """int[..., K, N] magnitudes -> packed uint8[..., cols, ceil(K/8), N].

    The *serving* operand layout (kernels/cim_matmul packed mode): the plane
    axis comes first (plane 0 = LSB, same column order as every other packed
    representation here), and the contraction axis K is packed MSB-first into
    bytes — the byte convention :func:`pack_rows` uses, so pool state and
    serving operands share one bit order.  K-padding bits are zero (pristine
    cells) and the matching activation rows are zero-padded by the kernel
    wrapper, so padding never contributes to a dot product.
    """
    planes = bitplanes(q, cols)  # [..., K, N, cols]
    planes = jnp.moveaxis(planes, -1, -3)  # [..., cols, K, N]
    return jnp.packbits(planes.astype(jnp.uint8), axis=-2)


@jax.jit
def pack_linear_sign(sign: jax.Array) -> jax.Array:
    """+1/-1 int8[..., K, N] -> packed sign bits uint8[..., ceil(K/8), N].

    Bit convention: 1 = negative weight (sign applied digitally after the
    magnitude reconstruction, mirroring differential crossbar pairs).  Same
    MSB-first K packing as :func:`pack_linear_planes`; padding bits are zero,
    i.e. +1, which multiplies only zero-magnitude padding cells.
    """
    return jnp.packbits((sign < 0).astype(jnp.uint8), axis=-2)


def encode_planes(packed: jax.Array, codec: str = "raw", *, chains=None, pin_cols=0):
    """Canonical packed planes -> stored :class:`~repro.core.planes.PlaneSet`.

    The codec layer's entry point from the slicing side: what used to be
    "pack is the stored form" becomes pack -> encode.  ``codec`` is one of
    :data:`repro.core.planes.CODECS`; ``col_perm*`` codecs additionally need
    the programming ``chains`` to plan column orders against each section's
    actual predecessor.  ``decode_planes(encode_planes(p, c)) == p``
    byte-for-byte for every codec.
    """
    from repro.core import planes  # deferred: planes imports schedule -> bitslice

    return planes.encode(packed, codec, chains=chains, pin_cols=pin_cols)


def decode_planes(plane_set) -> jax.Array:
    """Stored :class:`~repro.core.planes.PlaneSet` (or a raw packed array)
    -> canonical packed uint8[S, ceil(rows/8), cols] planes."""
    if isinstance(plane_set, jax.Array) or not hasattr(plane_set, "decode"):
        return plane_set
    return plane_set.decode()


def section(flat: jax.Array, rows: int) -> tuple[jax.Array, int]:
    """Partition a flat array into crossbar sections of ``rows`` weights.

    Zero-pads the tail.  Returns (sections[S, rows], original_length).
    Zero padding is exact for both encodings' *transition* accounting: q=0
    rows have no active memristors in sign_magnitude, and in offset_binary the
    padding is sliced off before dequantization so its value never matters.
    """
    n = flat.shape[0]
    pad = (-n) % rows
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, rows), n


def unsection(sections: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`section`: drop padding, return flat[n]."""
    return sections.reshape(-1)[:n]


def section_planes(q: jax.Array, rows: int, cols: int) -> tuple[jax.Array, int]:
    """int32[n] magnitudes -> bool[S, rows, cols] section bit planes."""
    sec, n = section(q, rows)
    return bitplanes(sec, cols), n
