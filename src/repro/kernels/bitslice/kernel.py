"""Pallas TPU kernel for fused quantize + bit-plane extraction.

Deploying a model to crossbars bit-slices every weight tensor; doing the
quantize->shift->mask pipeline in one VMEM pass avoids materializing the
intermediate int32 q tensor in HBM (at cols=10, that intermediate alone is
4 bytes/weight vs the 1-byte/plane output).  All VPU integer ops.

Grid: (K/bk, N/bn); each step writes all ``cols`` planes of its tile, so the
output block is (cols, bk, bn) and the plane axis is never re-visited.
``inv_scale`` rides in SMEM as a (1, 1) scalar block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import cdiv


def _kernel(scale_ref, w_ref, o_ref, *, cols: int):
    w = w_ref[...].astype(jnp.float32)
    inv_scale = scale_ref[0, 0]
    levels = jnp.float32(2**cols - 1)
    q = jnp.clip(jnp.round(jnp.abs(w) * inv_scale), 0.0, levels).astype(jnp.int32)
    sign = jnp.where(w < 0, -1, 1).astype(jnp.int32)
    for b in range(cols):
        o_ref[b, :, :] = (((q >> b) & 1) * sign).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("cols", "bk", "bn", "interpret"))
def bitslice_kernel(
    w: jax.Array,
    inv_scale: jax.Array,
    *,
    cols: int,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel entry: (K, N) must already be padded to block multiples."""
    k, n = w.shape
    grid = (cdiv(k, bk), cdiv(n, bn))
    return pl.pallas_call(
        functools.partial(_kernel, cols=cols),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((cols, bk, bn), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((cols, k, n), jnp.int8),
        interpret=interpret,
        name="bitslice_kernel",
    )(inv_scale.reshape(1, 1).astype(jnp.float32), w)
