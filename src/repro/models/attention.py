"""Memory-bounded attention for training, prefill, and decode.

``blockwise_attention`` is a pure-JAX flash-style attention: an online
softmax over key/value blocks carried through ``lax.scan``, so the (Sq, Sk)
score matrix is never materialized — peak memory is O(Sq * block_k) per
head.  This is the framework's default attention everywhere (a 32k prefill
with materialized scores would need terabytes; see DESIGN.md §5).  GQA/MQA
is handled by *grouping queries* (B, Hkv, G, Sq, D) rather than repeating
KV, so KV bytes stay at the GQA-reduced size.

The Pallas flash-attention kernel (repro.kernels.flash_attention) implements
the same contract for TPU; this module is the XLA-compilable path used by
the dry-run (Mosaic kernels cannot lower on the CPU dry-run backend).

Mask kinds: "causal", "bidir", "swa" (sliding window, causal).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Attention implementation switches (perf variants; see EXPERIMENTS.md §Perf).
# Mutated via set_attention_impl() BEFORE tracing — they select which HLO is
# lowered, exactly like a compile-time config in a production stack.
_IMPL = {"swa_banded": False, "swa_block_q": 512}


def set_attention_impl(*, swa_banded: bool | None = None, swa_block_q: int | None = None):
    if swa_banded is not None:
        _IMPL["swa_banded"] = swa_banded
    if swa_block_q is not None:
        _IMPL["swa_block_q"] = swa_block_q


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    kind: str = "causal",
    window: Optional[int] = None,
    q_offset: int = 0,
    block_k: int = 1024,
) -> jax.Array:
    """Implementation-dispatching attention entry point used by all blocks."""
    if kind == "swa" and _IMPL["swa_banded"] and isinstance(q_offset, int):
        return banded_swa_attention(
            q, k, v, window=window, q_offset=q_offset, block_q=_IMPL["swa_block_q"]
        )
    return blockwise_attention(
        q, k, v, kind=kind, window=window, q_offset=q_offset, block_k=block_k
    )


def _block_mask(
    q_pos: jax.Array, k_pos: jax.Array, kind: str, window: Optional[int]
) -> jax.Array:
    """(..., Sq, bk) boolean visibility mask from absolute positions;
    ``q_pos`` is (Sq,) or (B, Sq) for per-row offsets."""
    qp = q_pos[..., None]
    kp = k_pos
    if kind == "bidir":
        return jnp.ones(q_pos.shape + (k_pos.shape[0],), dtype=jnp.bool_)
    mask = kp <= qp
    if kind == "swa":
        assert window is not None
        mask = jnp.logical_and(mask, kp > qp - window)
    return mask


@functools.partial(jax.jit, static_argnames=("window", "q_offset", "block_q"))
@jax.named_scope("attention")
def banded_swa_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    q_offset: int = 0,
    block_q: int = 512,
) -> jax.Array:
    """Sliding-window attention that only computes the live band.

    The full blockwise path scores every (q, k) pair and masks — quadratic
    FLOPs even though SWA only reads a ``window``-wide band.  Here q is
    processed in blocks of ``block_q``; each block attends to a static-shape
    band of ``window + block_q`` keys fetched by dynamic_slice, so FLOPs and
    bytes are O(S * (window + block_q)) instead of O(S^2) — the §Perf lever
    that linearizes Hymba's 29 SWA layers at 32k prefill.

    Same contract as ``blockwise_attention(kind="swa")``: k/v hold positions
    [0, Sk); q holds positions [q_offset, q_offset + Sq).  ``q_offset`` must
    be a static int.  q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    scale = d**-0.5
    band = window + block_q

    nq = -(-sq // block_q)
    q_pad = nq * block_q - sq
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    # pad keys left by `window` (so the first band exists) and right so the
    # last band's slice is in-bounds: last start = q_offset + (nq-1)*block_q
    pad_r = max(0, q_offset + nq * block_q - sk)
    kp = jnp.pad(k, ((0, 0), (0, 0), (window, pad_r), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (window, pad_r), (0, 0)))
    qg = q.reshape(b, hkv, g, nq * block_q, d)

    def one_block(i):
        q_lo = i * block_q
        qb = jax.lax.dynamic_slice_in_dim(qg, q_lo, block_q, axis=3)
        # first needed key position: q_offset + q_lo - window + 1; slice one
        # earlier for simplicity -> padded-coords start = q_offset + q_lo
        kb = jax.lax.dynamic_slice_in_dim(kp, q_offset + q_lo, band, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vp, q_offset + q_lo, band, axis=2)
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qb.astype(jnp.float32), kb.astype(jnp.float32)
        ) * scale
        q_pos = q_offset + q_lo + jnp.arange(block_q)[:, None]
        k_pos = q_offset + q_lo - window + jnp.arange(band)[None, :]
        mask = (k_pos <= q_pos) & (k_pos > q_pos - window) & (k_pos >= 0) & (k_pos < sk)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        # softmax stays f32; the PV matmul runs with bf16 probabilities
        # (p <= 1, standard flash-kernel practice) — halves the p round-trip,
        # the banded path's largest remaining HBM term (§Perf cell-3 iter 2).
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vb, preferred_element_type=jnp.float32
        )

    blocks = jax.lax.map(one_block, jnp.arange(nq))  # (nq, B, Hkv, G, bq, Dv)
    out = jnp.moveaxis(blocks, 0, 3).reshape(b, hkv, g, nq * block_q, dv)
    return out[:, :, :, :sq].reshape(b, hq, sq, dv).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("kind", "window", "block_k", "skip_masked_blocks")
)
@jax.named_scope("attention")
def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    kind: str = "causal",
    window: Optional[int] = None,
    q_offset: int | jax.Array = 0,
    block_k: int = 1024,
    skip_masked_blocks: bool = False,
    kv_valid_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Online-softmax attention.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0.
    q_offset: absolute position of q[0] (prefill continuation / decode);
      scalar, or a (B,) vector of per-row offsets (batched ragged prefill
      chunks — every row of the batch sits at its own prompt position, as
      in the engine's fused prefill+decode dispatches).
    kv_valid_len: optional scalar or (B,) vector — positions >= it are
      masked (cache tail / per-slot valid lengths).  The Pallas flash
      kernel (repro.kernels.flash_attention) implements the same per-row
      contract with both values traced in SMEM.
    skip_masked_blocks: when True, fully-masked key blocks contribute via a
      zero multiplier (their matmuls still run under scan; the *compile-time
      skip* variant is a hillclimb lever — see EXPERIMENTS.md §Perf).

    Returns (B, Hq, Sq, D) in q.dtype.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    dv = v.shape[-1]  # v head dim may differ from qk head dim (MLA)
    g = hq // hkv
    assert hq == hkv * g, (hq, hkv)
    scale = d**-0.5

    nk = -(-sk // block_k)
    pad = nk * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(b, hkv, g, sq, d)
    # (Sq,) shared positions, or (B, Sq) per-row; masks broadcast over the
    # batch axis either way (the scalar path is bit-identical to before)
    off = jnp.asarray(q_offset)
    q_pos = (off[..., None] + jnp.arange(sq)) if off.ndim else off + jnp.arange(sq)
    vl = None if kv_valid_len is None else jnp.reshape(jnp.asarray(kv_valid_len), (-1, 1))

    def step(carry, kj):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, kj * block_k, block_k, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v, kj * block_k, block_k, axis=2)
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), kb.astype(jnp.float32)
        ) * scale
        k_pos = kj * block_k + jnp.arange(block_k)
        mask = _block_mask(q_pos, k_pos, kind, window)  # (Sq, bk) or (B, Sq, bk)
        valid = k_pos < sk if not pad else k_pos < (sk)
        if vl is not None:
            valid = jnp.logical_and(valid, k_pos[None, :] < vl)  # (1|B, bk)
        mask = jnp.logical_and(mask, valid[..., None, :])
        if mask.ndim == 2:
            mask = mask[None]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), jnp.arange(nk))

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid_len: jax.Array,
    *,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-step attention against a (possibly partially filled) KV cache.

    q: (B, Hq, 1, D); caches: (B, Hkv, S, D); valid_len: scalar int — number
    of valid cache positions (the new token's KV must already be written) —
    or a (B,) vector of per-row lengths (ragged continuous-batching decode:
    every slot sits at its own position in its own sequence).

    Runs the fixed-size key blocks of :func:`blockwise_attention`, so the
    result does not depend on how long the cache view is: blocks past
    ``valid_len`` add exact zeros, and a one-shot softmax over the whole
    view would sum its terms in an order that changes with the view length
    (on TPU, the engine's bucketed page views and a solo request's cache
    then gave different tokens).
    """
    vl = jnp.asarray(valid_len)
    return blockwise_attention(
        q, k_cache, v_cache, kind="swa" if window is not None else "causal",
        window=window, q_offset=vl - 1, kv_valid_len=vl,
    )
