"""Pallas TPU kernel for packed Hamming transition counting (Eq. 1).

The planner's dominant compute when pricing large models is XOR+popcount
over millions of packed section pairs.  Each section's ``W x C`` packed
bytes are flattened onto the lane axis, so a grid step loads a
``(bt, W*C)`` block of both operands into VMEM, XORs on the VPU and
popcounts with a SWAR shift/mask sequence (portable across Mosaic and the
interpreter).  The per-pair sums come out of one small MXU product,
``ones(8, W*C) @ popcounts.T``, whose rows are the counts laid along the
lanes: the ``(bt,)`` output block is then lane-dense.  Mosaic has no layout
for a VPU reduction into a 1-D block (the old ``[bt, W, C]`` block with a
``sum(axis=(1, 2))`` failed to compile for v5e with "Invalid output
layout"), and a ``(bt, 1)`` column output pads every count to a full
128-lane row in HBM.  The product is exact: popcounts are integers <= 8,
exact in any MXU input precision, and their f32 sums stay below 2**24.

Blocks are sized so the int32 working set stays well under VMEM: the
default bt=1024 with 128x10 sections is ``1024 x 160`` bytes of input per
operand per step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._util import cdiv, popcount_i32


def _kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)
    pc = popcount_i32(jnp.bitwise_xor(a, b)).astype(jnp.float32)
    ones = jnp.ones((8, pc.shape[1]), jnp.float32)
    sums = jax.lax.dot_general(
        ones, pc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] = sums[0].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def hamming_pairs_kernel(
    a: jax.Array, b: jax.Array, *, bt: int = 1024, interpret: bool = False
) -> jax.Array:
    """Raw kernel entry: bt is T itself or a multiple of 32 (uint8 sublane tile).

    a, b: uint8[T, ...] (e.g. [T, W, C] sections) -> int32[T].  A ragged
    last block reads rows past T, but each output row depends only on its
    own input row and writes past T are dropped, so no padding copy is
    needed.
    """
    t = a.shape[0]
    assert bt == t or bt % 32 == 0, f"bt={bt} must be T={t} or a multiple of 32"
    wc = math.prod(a.shape[1:])
    return pl.pallas_call(
        _kernel,
        grid=(cdiv(t, bt),),
        in_specs=[
            pl.BlockSpec((bt, wc), lambda i: (i, 0)),
            pl.BlockSpec((bt, wc), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((t,), jnp.int32),
        interpret=interpret,
        name="hamming_pairs_kernel",
    )(a.reshape(t, wc), b.reshape(t, wc))
