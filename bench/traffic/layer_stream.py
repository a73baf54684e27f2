"""Layer-by-layer redeploy traffic: each transformer layer's matrices, checkpoint after checkpoint.

The crossbars are reprogrammed layer after layer (the paper's workload):
layers 0..L-1 of checkpoint 0, then of checkpoint 1, and so on.  A layer is
the matrices of one attention block and one SwiGLU MLP at the
configuration's published widths.  Checkpoint 0's weights are a truncated
normal at the fan-in scale (the model initializer's); each later checkpoint
adds seeded drift, ``w_c = w_{c-1} + drift * z_c / sqrt(fan_in)``.  Every
tensor is made on the device from (seed, checkpoint, layer, name), so any
tensor can be made again alone.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import weights as W


def matrices(model: dict) -> dict[str, tuple[int, int]]:
    """The matrices of one layer, in the order a pytree flattens them."""
    d, f, hd = model["d_model"], model["d_ff"], model["head_dim"]
    q_out, kv_out = model["n_heads"] * hd, model["n_kv_heads"] * hd
    return {
        "attn/wk": (d, kv_out), "attn/wo": (q_out, d), "attn/wq": (d, q_out),
        "attn/wv": (d, kv_out), "mlp/wi_gate": (d, f), "mlp/wi_up": (d, f), "mlp/wo": (f, d),
    }


@functools.partial(jax.jit, static_argnames=("shape", "drift"))
def _tensor(key, ckpt, layer, shape, drift):
    k = jax.random.fold_in(key, layer)
    scale = 1.0 / math.sqrt(shape[0])
    w = jax.random.truncated_normal(jax.random.fold_in(k, 0), -3.0, 3.0, shape) * scale

    def add(c, acc):
        z = jax.random.normal(jax.random.fold_in(k, c), shape)
        return acc + drift * scale * z

    return jax.lax.fori_loop(1, ckpt + 1, add, w)


def tensor(seed: int, model: dict, drift: float, ckpt: int, layer: int, name: str) -> jax.Array:
    key = W.leaf_key(W.base_key(seed), name)
    return _tensor(key, jnp.int32(ckpt), jnp.int32(layer), matrices(model)[name], float(drift))


def layer_params(seed: int, model: dict, drift: float, ckpt: int, layer: int) -> dict:
    """The nested parameter dict of one layer, as the planner is given it."""
    out: dict = {}
    for name in matrices(model):
        blk, leaf = name.split("/")
        out.setdefault(blk, {})[leaf] = tensor(seed, model, drift, ckpt, layer, name)
    return out


def order(model: dict):
    """(checkpoint, layer) in redeploy order, without end."""
    k = 0
    while True:
        yield divmod(k, model["n_layers"])
        k += 1
