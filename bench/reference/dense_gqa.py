"""Plain jax.numpy reference of a dense GQA decoder (the serving cells' yardstick).

Straightforward ``jax.numpy`` in float32 with no kernels, no KV cache, no
paging and no batching across requests, written from the layer equations.
Per layer (pre-norm, residual):

    h   = x + Wo · attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x))   (causal GQA)
    x'  = h + Wd · (silu(Wg·n2(h)) * Wu·n2(h))                        (SwiGLU)

then ``logits = Whead · n(x)``, with ``n`` an RMSNorm with a learned gain.
Query head ``i`` reads KV head ``i // (n_heads / n_kv_heads)``.

One departure from the published InternLM2 and Yi descriptions, shared with
the system under test: RoPE rotates adjacent channel pairs ``(2i, 2i+1)``
where the published code rotates ``(i, i + d/2)`` (the same rotation up to a
fixed permutation of the q/k output columns).  The RMSNorm epsilon is the
configuration's own (``rms_norm_eps``).

It exposes what the serving harness asks of a configuration's reference
(``bench/reference/__init__.py``): ``dims``, ``leaves``, ``Weights`` and
``served_gaps``.  Weights come from ``bench.weights`` one layer at a time,
from the seed, so a full-width model's reference fits on one chip.  Every
matmul runs at ``precision="highest"``; ``rounding`` rounds the inputs of
every matmul to a lower type first (the control: ``float8_e4m3fn``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, weights as W


def _round(a: jax.Array, rounding) -> jax.Array:
    return a if rounding is None else a.astype(rounding).astype(jnp.float32)


def _mm(x, w, rounding):
    return jnp.dot(_round(x, rounding), _round(w, rounding),
                   precision="highest", preferred_element_type=jnp.float32)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, S, H, hd) at positions 0..S-1; rotates pairs (2i, 2i+1)."""
    s, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dims", "rounding"))
def _layer(x, p, dims, rounding):
    h, hkv, hd, theta, eps = dims
    b, s, _ = x.shape
    y = _rmsnorm(x, p["ln1"], eps)
    q = _rope(_mm(y, p["wq"], rounding).reshape(b, s, h, hd), theta)
    k = _rope(_mm(y, p["wk"], rounding).reshape(b, s, hkv, hd), theta)
    v = _mm(y, p["wv"], rounding).reshape(b, s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, rounding), _round(k, rounding),
                        precision="highest", preferred_element_type=jnp.float32) * hd**-0.5
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, rounding), _round(v, rounding),
                   precision="highest", preferred_element_type=jnp.float32).reshape(b, s, h * hd)
    x = x + _mm(o, p["wo"], rounding)
    y = _rmsnorm(x, p["ln2"], eps)
    m = jax.nn.silu(_mm(y, p["wi_gate"], rounding)) * _mm(y, p["wi_up"], rounding)
    return x + _mm(m, p["wd"], rounding)


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _head(x, g, w, eps, rounding):
    return _mm(_rmsnorm(x, g, eps), w, rounding)


def dims(conf: dict) -> dict:
    """The sizes this reference and its operation count
    (``bench/roofline/dense_gqa.py``) use, from a config file's HF keys."""
    return common.model_dims(conf)


def leaves(dims: dict) -> list[tuple[str, tuple[int, ...], bool]]:
    """(name, shape, layer-stacked) of every leaf of the program's parameter
    tree for a dense GQA decoder: one segment of layer-stacked blocks."""
    L, d, f, v = dims["n_layers"], dims["d_model"], dims["d_ff"], dims["vocab_size"]
    hd = dims["head_dim"]
    q_out, kv_out = dims["n_heads"] * hd, dims["n_kv_heads"] * hd
    return [
        ("embed/table", (v, d), False),
        ("final_norm/g", (d,), False),
        ("head/w", (d, v), False),
        ("segments/0/attn/wk", (L, d, kv_out), True),
        ("segments/0/attn/wo", (L, q_out, d), True),
        ("segments/0/attn/wq", (L, d, q_out), True),
        ("segments/0/attn/wv", (L, d, kv_out), True),
        ("segments/0/ln1/g", (L, d), True),
        ("segments/0/ln2/g", (L, d), True),
        ("segments/0/mlp/wi_gate", (L, d, f), True),
        ("segments/0/mlp/wi_up", (L, d, f), True),
        ("segments/0/mlp/wo", (L, f, d), True),
    ]


class Weights:
    """The seeded weights of one configuration, made one leaf slice at a time."""

    _LAYER = {"wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo",
              "ln1": "ln1/g", "ln2": "ln2/g", "wi_gate": "mlp/wi_gate",
              "wi_up": "mlp/wi_up", "wd": "mlp/wo"}

    def __init__(self, model: dict, seed: int):
        self.model = model
        self.key = W.base_key(seed)
        self.leaves = {leaf["name"]: leaf for leaf in W.layout(leaves(model))}
        self._fns = {}

    def get(self, name: str, layer: int = 0) -> jax.Array:
        if name not in self._fns:
            leaf = self.leaves[name]
            self._fns[name] = jax.jit(lambda key, i: W.leaf_slice(key, leaf, i))
        return self._fns[name](self.key, jnp.int32(layer))

    def layer(self, i: int) -> dict:
        return {k: self.get("segments/0/" + v, i) for k, v in self._LAYER.items()}


def hidden(wts: Weights, tokens: list[np.ndarray], *, rounding=None) -> list[jax.Array]:
    """Final hidden states f32[1, S, d] of each int32[S] sequence; layers run
    outermost, so each layer's weights are made once for all sequences."""
    m = wts.model
    dims = (m["n_heads"], m["n_kv_heads"], m["head_dim"], float(m["rope_theta"]),
            float(m["rms_norm_eps"]))
    with jax.default_matmul_precision("highest"):
        table = wts.get("embed/table")
        xs = [table[jnp.asarray(t)[None]] for t in tokens]
        del table
        for i in range(m["n_layers"]):
            p = wts.layer(i)
            xs = [_layer(x, p, dims, rounding) for x in xs]
        return xs


@jax.jit
def _gaps(ref, other, picked):
    """Per position: how far the picked token's reference logit lies below the
    reference's best.  ``picked`` < 0 takes the argmax of ``other``."""
    tok = jnp.where(picked >= 0, picked, jnp.argmax(other, axis=-1))
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]


def served_gaps(wts: Weights, seqs: list[tuple[np.ndarray, list[int]]], pad_to: int,
                *, control=None) -> np.ndarray:
    """Gap of every served token under the float32 reference.

    ``seqs`` holds (prompt, served tokens) pairs; each runs alone, padded at
    the end to ``pad_to`` positions (causal attention: padding never reaches
    an earlier position, and one compiled shape serves every request).  With
    ``control`` (a lower type), the gap is that of the token the control's
    own logits put first at each served position, not the served token.
    """
    toks, spans = [], []
    for prompt, served in seqs:
        full = np.concatenate([prompt, np.asarray(served, np.int32)]).astype(np.int32)
        t = np.zeros((pad_to,), np.int32)
        t[: full.size] = full
        toks.append(t)
        spans.append((prompt.size - 1, full.size - 1))  # positions predicting served tokens
    g, w = wts.get("final_norm/g"), wts.get("head/w")
    eps = float(wts.model["rms_norm_eps"])
    ref_h = hidden(wts, toks)
    ctl_h = hidden(wts, toks, rounding=control) if control is not None else None
    out = []
    for j, ((lo, hi), (_, served)) in enumerate(zip(spans, seqs)):
        ref = _head(ref_h[j], g, w, eps, None)[0, lo:hi]
        if ctl_h is None:
            other, picked = ref, jnp.asarray(np.asarray(served, np.int32))
        else:
            other = _head(ctl_h[j], g, w, eps, control)[0, lo:hi]
            picked = -jnp.ones((hi - lo,), jnp.int32)
        out.append(np.asarray(_gaps(ref, other, picked)))
    return np.concatenate(out) if out else np.zeros((0,), np.float32)
