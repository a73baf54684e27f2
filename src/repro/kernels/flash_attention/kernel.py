"""Pallas TPU flash-attention kernel (forward).

IO-aware attention for the 32k-prefill cells: the (Sq, Sk) score matrix is
never materialized in HBM.  Grid is (B, Hq, Sq/bq, Sk/bk) with the key axis
innermost; the online-softmax statistics (m, l) and the output accumulator
live in VMEM scratch across the k loop, so each q tile is read once and
each k/v tile is read once per q tile.

GQA without KV expansion: the k/v BlockSpec index_map divides the query
head index by the group size, so KV HBM traffic stays at the GQA-reduced
size (the reason GQA helps the memory roofline term at 32k).

Causal/SWA tiles that are fully masked are skipped with ``pl.when`` on the
*block* indices — the compile-time analogue of FlashAttention's block
skipping, worth ~2x on causal prefill (half the tiles are dead).

Ragged serving support: ``q_offsets`` and ``kv_valid_len`` are *traced
per-row* scalars living in SMEM, indexed by the batch grid axis — one
compiled kernel serves every mix of per-request prompt positions and cache
valid lengths (the fused prefill+decode dispatch batches rows at different
absolute positions with different live-cache extents).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import cdiv

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, qoff_ref, kvl_ref, o_ref, m_ref, l_ref, acc_ref,
    *, kind: str, window: Optional[int], bq: int, bk: int,
    n_k: int, sk_valid: int, scale: float,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-row traced scalars (SMEM, indexed by the batch grid axis):
    # absolute position of this row's q[0], and its live cache extent
    q_lo = qoff_ref[0, 0] + iq * bq  # absolute position of this q tile's 1st row
    k_lo = ik * bk
    kvl = kvl_ref[0, 0]

    def body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        # static padding tail AND the traced per-row valid length (paged
        # serving: the gathered cache view's tail holds stale pool bytes)
        mask = jnp.logical_and(k_pos < sk_valid, k_pos < kvl)
        if kind != "bidir":
            mask = jnp.logical_and(mask, k_pos <= q_pos)
            if kind == "swa":
                mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if kind == "bidir":
        body()
    else:
        # causal block skip: tile is dead iff its first key position exceeds
        # the last query position (and for SWA, iff it is entirely behind the
        # window of the last query row).
        live = k_lo <= q_lo + bq - 1
        # tiles entirely past the traced valid length are dead too (the cache
        # view's unwritten tail in paged serving)
        live = jnp.logical_and(live, k_lo < kvl)
        if kind == "swa":
            live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)
        pl.when(live)(body)

    @pl.when(ik == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "window", "bq", "bk", "sk_valid", "interpret"),
)
def flash_attention_kernel(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offsets: jax.Array,
    kv_valid_len: jax.Array,
    *,
    kind: str = "causal",
    window: Optional[int] = None,
    bq: int = 128,
    bk: int = 128,
    sk_valid: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel entry: Sq % bq == 0 and Sk % bk == 0 required.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D] -> [B, Hq, Sq, D].
    ``q_offsets``: (B,) i32 traced per-row absolute position of each row's
    q[0] — rows of a ragged dispatch sit at their own prompt positions.
    ``kv_valid_len``: (B,) i32 traced per-row live cache extents — key
    positions >= a row's extent are masked without recompiling (continuous-
    batching rows attend to a fixed-shape view whose valid length differs
    per slot and grows per chunk).  ``sk_valid`` masks the *static* padding
    tail.  Callers wanting the historical scalar behaviour broadcast one
    value (ops.py does).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    assert hq == hkv * g, (hq, hkv)
    n_q, n_k = cdiv(sq, bq), cdiv(sk, bk)
    sk_valid = sk if sk_valid is None else sk_valid
    qoff = jnp.reshape(jnp.asarray(q_offsets, jnp.int32), (b, 1))
    kvl = jnp.reshape(jnp.asarray(kv_valid_len, jnp.int32), (b, 1))
    grid = (b, hq, n_q, n_k)

    kern = functools.partial(
        _kernel,
        kind=kind, window=window,
        bq=bq, bk=bk, n_k=n_k, sk_valid=sk_valid, scale=d**-0.5,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec(
                (1, 1), lambda ib, ih, iq, ik: (ib, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (1, 1), lambda ib, ih, iq, ik: (ib, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_kernel",
    )(q, k, v, qoff, kvl)
