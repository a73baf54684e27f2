"""Dense transformer blocks (pre-norm attention + gated MLP).

Every block kind in this framework exposes the same pair of functions:

  init_<kind>(key, cfg)                       -> layer params (unstacked)
  <kind>_fwd(p, cfg, x, *, q_offset, return_cache, layer_flag)
                                              -> (x, cache | None)
  <kind>_step(p, cfg, x, cache, pos, *, layer_flag)
                                              -> (x, cache)

``layer_flag`` is a traced per-layer scalar threaded through ``lax.scan``
(used e.g. by Hymba to switch SWA <-> global attention without breaking the
homogeneous-stack scan).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.models.attention import attention, blockwise_attention, decode_attention
from repro.models.layers import Params


# ---------------------------------------------------------------------------
# Tensor parallelism: cross-shard reduction points
#
# Under a TP plan (parallel/tp.py) this module runs as ONE shard: q/k/v and
# wi_gate/wi_up are column-parallel (cfg already holds the shard-local head /
# d_ff counts), wo is row-parallel, so each shard's wo output is a PARTIAL
# sum over its slice of the contraction axis.  The reduction must happen
# before the residual add (residual + norms are replicated), which is why the
# psum sits here at the block call sites and not inside layers.linear.
# ---------------------------------------------------------------------------

def _tp_reduce(y: jax.Array, cfg: ArchConfig, enabled: bool) -> jax.Array:
    """psum partial row-parallel outputs over cfg.tp_axis (no-op untagged)."""
    if enabled and cfg.tp_axis is not None:
        return jax.lax.psum(y, cfg.tp_axis)
    return y


# ---------------------------------------------------------------------------
# GQA/MQA attention sub-layer
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig) -> Params:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": layers._dense_init(k1, cfg.d_model, cfg.n_heads * hd),
        "wk": layers._dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd),
        "wv": layers._dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd),
        "wo": layers._dense_init(k4, cfg.n_heads * hd, cfg.d_model),
    }


def _qkv(p: Params, cfg: ArchConfig, x: jax.Array, positions: jax.Array):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    dtype = x.dtype
    q = layers.linear(p["wq"], x, dtype).reshape(b, s, cfg.n_heads, hd)
    k = layers.linear(p["wk"], x, dtype).reshape(b, s, cfg.n_kv_heads, hd)
    v = layers.linear(p["wv"], x, dtype).reshape(b, s, cfg.n_kv_heads, hd)
    # positions: (S,) shared by the batch, or (B, S) per-row (ragged decode
    # slots each sit at their own absolute position)
    pos_b = positions if positions.ndim == 2 else positions[None]
    q = layers.apply_rope(q.transpose(0, 2, 1, 3), pos_b[:, None, :], cfg.rope_theta)
    k = layers.apply_rope(k.transpose(0, 2, 1, 3), pos_b[:, None, :], cfg.rope_theta)
    v = v.transpose(0, 2, 1, 3)
    return q, k, v  # (B, H, S, hd)


def attention_fwd(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    *,
    q_offset: int = 0,
    kind: str = "causal",
    window: Optional[int] = None,
    return_cache: bool = False,
):
    b, s, _ = x.shape
    positions = q_offset + jnp.arange(s)
    q, k, v = _qkv(p, cfg, x, positions)
    out = attention(q, k, v, kind=kind, window=window, q_offset=q_offset)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    y = _tp_reduce(layers.linear(p["wo"], out, x.dtype), cfg, cfg.tp_attn)
    cache = {"k": k, "v": v} if return_cache else None
    return y, cache


def attention_step(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    cache: dict[str, jax.Array],
    pos: jax.Array,
    *,
    window: Optional[jax.Array] = None,
):
    """x: (B, 1, d); cache k/v: (B, Hkv, S, hd); pos: scalar index to write,
    or a (B,) vector of per-row indices (ragged continuous-batching decode)."""
    b = x.shape[0]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        q, k, v = _qkv(p, cfg, x, jnp.reshape(pos, (1,)))
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=2)
    else:
        q, k, v = _qkv(p, cfg, x, pos[:, None])
        upd = jax.vmap(
            lambda c, new, p_: jax.lax.dynamic_update_slice_in_dim(c, new, p_, axis=1)
        )
        k_cache = upd(cache["k"], k, pos)
        v_cache = upd(cache["v"], v, pos)
    out = decode_attention(q, k_cache, v_cache, pos + 1, window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    y = _tp_reduce(layers.linear(p["wo"], out, x.dtype), cfg, cfg.tp_attn)
    return y, {"k": k_cache, "v": v_cache}


def init_attn_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype) -> dict[str, Any]:
    hd = cfg.resolved_head_dim
    shape = (batch, cfg.n_kv_heads, seq_len, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Paged KV attention (continuous-batching engine)
#
# The physical cache is a token-major pool shared by every slot:
# k/v: (T, Hkv, hd) with T = num_blocks * page_size.  A slot owns a list of
# fixed-size pages — its block-table row ``table`` (B, P) — mapping logical
# positions to physical cells.  A dispatch gathers each slot's pages ONCE
# into a contiguous (B, Hkv, L, hd) cache view (one gather index per page,
# contiguous page copies), runs ordinary contiguous-cache steps against it
# (``attention_step`` with per-row positions / ``attention_chunk_step``),
# and scatters only the newly written cells back afterwards — so the
# per-token step math is shared with the static path, and decode quanta pay
# the gather once per dispatch instead of once per token.  View positions
# past a slot's valid length hold stale pool bytes; they are masked to
# NEG_INF before the softmax max, so outputs are bit-identical to a
# contiguous cache (see tests/test_engine.py).
# ---------------------------------------------------------------------------

def init_attn_pool(cfg: ArchConfig, num_tokens: int, dtype) -> dict[str, Any]:
    """Token-major physical KV pool: k/v (T, Hkv, hd)."""
    hd = cfg.resolved_head_dim
    shape = (num_tokens, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@jax.named_scope("kv_gather")
def gather_pool_view(pool_arr: jax.Array, table: jax.Array, page_size: int) -> jax.Array:
    """(..., T, Hkv, hd) pool + (B, P) block table -> (..., B, Hkv, L, hd)
    contiguous per-slot cache view, L = P * page_size."""
    *lead, t, hkv, hd = pool_arr.shape
    b, p = table.shape
    paged = pool_arr.reshape(*lead, t // page_size, page_size, hkv, hd)
    view = jnp.take(paged, table.reshape(-1), axis=len(lead)).reshape(
        *lead, b, p * page_size, hkv, hd
    )
    return jnp.moveaxis(view, -2, -3)


@jax.named_scope("kv_scatter")
def scatter_pool_view(
    pool_arr: jax.Array,
    view: jax.Array,
    table: jax.Array,
    pos0: jax.Array,
    n_tokens: int,
    page_size: int,
) -> jax.Array:
    """Write back the cells a dispatch filled: view positions
    [pos0_r, pos0_r + n_tokens) of each row r land in their physical pool
    cells (dummy-page rows absorb padded writes).  view: (..., B, Hkv, L,
    hd); returns the updated (..., T, Hkv, hd) pool."""
    *lead, b, hkv, l, hd = view.shape
    idx = pos0[:, None] + jnp.arange(n_tokens)  # (B, n) logical positions
    blk = jnp.take_along_axis(table, idx // page_size, axis=1)
    flat = (blk * page_size + idx % page_size).reshape(-1)  # (B*n,) pool cells
    # extract written tokens: (..., B, Hkv, n, hd) -> (..., B*n, Hkv, hd)
    got = jnp.take_along_axis(
        view, idx.reshape((1,) * len(lead) + (b, 1, n_tokens, 1)), axis=-2
    )
    got = jnp.moveaxis(got, -3, -2).reshape(*lead, b * n_tokens, hkv, hd)
    if lead:
        return pool_arr.at[:, flat].set(got)
    return pool_arr.at[flat].set(got)


def attention_chunk_step(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    cache: dict[str, jax.Array],
    start: jax.Array,
    kv_len: jax.Array,
    *,
    kind: str = "causal",
    window: Optional[int] = None,
):
    """Multi-token continuation against a contiguous cache view, B rows wide.

    x: (B, C, d) — row r holds chunk positions [start_r, start_r + C) of its
    own request (tail columns past a row's true chunk length are padding —
    causality plus ``kv_len`` masking keep them invisible, and the caller's
    write-back routing sends them to the dummy page); cache k/v:
    (B, Hkv, L, hd); start/kv_len: scalars or (B,) per-row vectors,
    ``kv_len`` the valid cache length after this chunk.  Causality makes
    chunked prefill equal full prefill; the shared blockwise-attention
    kernel with traced per-row ``q_offset`` keeps each row bit-identical to
    its solo prefill (key blocks partition the same way — padding only
    appends masked columns).  Extent-1 decode rows do NOT ride this path:
    the engine's fused dispatch runs them through the decode-quantum scan
    sub-batch (``launch.steps._ragged_scan_body``), whose single-step
    ``decode_attention`` normalization is the one solo decode uses.
    """
    b, c, _ = x.shape
    start = jnp.asarray(start)
    positions = (start[:, None] if start.ndim else start) + jnp.arange(c)
    q, k, v = _qkv(p, cfg, x, positions)  # (B, H, C, hd)
    start_b = jnp.broadcast_to(jnp.atleast_1d(start), (b,))
    upd = jax.vmap(
        lambda cch, new, s: jax.lax.dynamic_update_slice_in_dim(cch, new, s, axis=1)
    )
    k_cache = upd(cache["k"], k, start_b)
    v_cache = upd(cache["v"], v, start_b)
    out = blockwise_attention(
        q, k_cache, v_cache, kind=kind, window=window, q_offset=start,
        kv_valid_len=kv_len,
    )
    out = out.transpose(0, 2, 1, 3).reshape(b, c, -1)
    y = _tp_reduce(layers.linear(p["wo"], out, x.dtype), cfg, cfg.tp_attn)
    return y, {"k": k_cache, "v": v_cache}


def attn_block_chunk_step(
    p: Params, cfg: ArchConfig, x, cache, start, kv_len,
    *, kind: str = "causal", window=None, **_,
):
    a, cache = attention_chunk_step(
        p["attn"], cfg, layers.rmsnorm(p["ln1"], x), cache, start, kv_len,
        kind=kind, window=window,
    )
    x = x + a
    m = layers.glu_mlp(p["mlp"], layers.rmsnorm(p["ln2"], x), cfg.act, x.dtype)
    x = x + _tp_reduce(m, cfg, cfg.tp_mlp)
    return x, cache


# ---------------------------------------------------------------------------
# Dense block: pre-norm attn + pre-norm gated MLP
# ---------------------------------------------------------------------------

def init_attn_block(key, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": layers.init_norm(cfg.d_model),
        "attn": init_attention(k1, cfg),
        "ln2": layers.init_norm(cfg.d_model),
        "mlp": layers.init_glu_mlp(k2, cfg.d_model, cfg.d_ff),
    }


def attn_block_fwd(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    *,
    q_offset: int = 0,
    kind: str = "causal",
    window=None,
    return_cache: bool = False,
    layer_flag=None,
):
    a, cache = attention_fwd(
        p["attn"], cfg, layers.rmsnorm(p["ln1"], x),
        q_offset=q_offset, kind=kind, window=window, return_cache=return_cache,
    )
    x = x + a
    m = layers.glu_mlp(p["mlp"], layers.rmsnorm(p["ln2"], x), cfg.act, x.dtype)
    x = x + _tp_reduce(m, cfg, cfg.tp_mlp)
    return x, cache


def attn_block_step(p: Params, cfg: ArchConfig, x, cache, pos, *, window=None, layer_flag=None, **_):
    a, cache = attention_step(p["attn"], cfg, layers.rmsnorm(p["ln1"], x), cache, pos, window=window)
    x = x + a
    m = layers.glu_mlp(p["mlp"], layers.rmsnorm(p["ln2"], x), cfg.act, x.dtype)
    x = x + _tp_reduce(m, cfg, cfg.tp_mlp)
    return x, cache
