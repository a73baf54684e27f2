"""Plain jax.numpy reference forward pass of a dense GQA decoder.

The yardstick the serving path is compared with on logits: straightforward
``jax.numpy`` with no kernels, no KV cache, no paging and no tensor
parallelism, written from the layer equations rather than from
``models.blocks``.  Per layer (pre-norm, residual):

    h   = x + Wo · attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x))   (causal GQA)
    x'  = h + Wd · (silu(Wg·n2(h)) * Wu·n2(h))                        (SwiGLU)

then ``logits = Whead · n(x)``, with ``n`` an RMSNorm with a learned gain.
Query head ``i`` reads KV head ``i // (n_heads / n_kv_heads)``.

Two departures from the published InternLM2 description, both shared with
``models/``: RoPE rotates adjacent channel pairs ``(2i, 2i+1)`` where the
published code rotates ``(i, i + d/2)`` (the same rotation up to a fixed
permutation of the q/k output columns), and the RMSNorm epsilon is 1e-6
where the published config has 1e-5.

Layers run one at a time (``params`` may hold host arrays; each layer's
weights go to the device only while it runs), so a full-width model's
reference fits beside a served deployment.  ``dtype`` sets the matmul input
precision: float32 (the reference proper) runs under
``jax.default_matmul_precision("highest")``, because a float32 matmul on a
TPU otherwise runs at reduced precision; bfloat16 rounds every matmul input
to bfloat16 (f32 accumulation), which measures how far plain bfloat16
arithmetic of the same math sits from the float32 result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


def _check(cfg: ArchConfig) -> None:
    if (cfg.encdec or cfg.moe or cfg.mla or cfg.ssm or cfg.tie_embeddings
            or cfg.embed_scale or cfg.n_meta_tokens or cfg.stub_prefix_len
            or cfg.act != "swiglu" or set(cfg.layer_kinds()) != {"attn"}):
        raise NotImplementedError(
            f"{cfg.name}: the reference covers dense full-attention SwiGLU decoders"
        )


def _mm(x: jax.Array, w: jax.Array, dtype) -> jax.Array:
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32)


def _rmsnorm(x: jax.Array, g: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd) at positions 0..S-1; rotates pairs (2i, 2i+1)."""
    s, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _layer(x: jax.Array, p: dict, cfg: ArchConfig, dtype) -> jax.Array:
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    y = _rmsnorm(x, p["ln1"]["g"])
    q = _rope(_mm(y, p["attn"]["wq"], dtype).reshape(b, s, h, hd), cfg.rope_theta)
    k = _rope(_mm(y, p["attn"]["wk"], dtype).reshape(b, s, hkv, hd), cfg.rope_theta)
    v = _mm(y, p["attn"]["wv"], dtype).reshape(b, s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(dtype), k.astype(dtype),
        preferred_element_type=jnp.float32,
    ) * hd**-0.5
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(dtype), v.astype(dtype),
        preferred_element_type=jnp.float32,
    ).reshape(b, s, h * hd)
    x = x + _mm(o, p["attn"]["wo"], dtype)
    y = _rmsnorm(x, p["ln2"]["g"])
    m = jax.nn.silu(_mm(y, p["mlp"]["wi_gate"], dtype)) * _mm(y, p["mlp"]["wi_up"], dtype)
    return x + _mm(m, p["mlp"]["wo"], dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _head(x: jax.Array, g: jax.Array, w: jax.Array, dtype) -> jax.Array:
    return _mm(_rmsnorm(x, g), w, dtype)


def logits(params, cfg: ArchConfig, tokens, *, dtype=jnp.float32) -> jax.Array:
    """Reference logits f32[B, S, V] for ``tokens`` int32[B, S].

    ``params`` is a dense parameter tree in the layout ``models.transformer``
    builds (layer-stacked ``segments[0]``); leaves may be host arrays.
    """
    _check(cfg)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["table"])[jnp.asarray(tokens)]
        stack = params["segments"][0]
        for i in range(cfg.n_layers):
            x = _layer(x, jax.tree.map(lambda a: f32(a[i]), stack), cfg, dtype)
        return _head(x, f32(params["final_norm"]["g"]), f32(params["head"]["w"]), dtype)
