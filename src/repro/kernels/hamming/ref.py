"""Pure-jnp oracle for packed Hamming transition counting.

Contract (shared with kernel.py / ops.py):
  a, b: uint8[T, W, C] packed bit planes (W = ceil(rows/8) byte words,
        C = bit columns); see ``repro.core.bitslice.pack_rows``.  Any
        trailing layout works: the count runs over every byte of a pair, so
        sections flattened to uint8[T, W*C] rows price identically.
  out:  int32[T] — per-pair transition counts: popcount(a[t] XOR b[t]).

This is Eq. 1 of the paper evaluated for T crossbar reprogram pairs at once;
the planner calls it with a = states[:-1], b = states[1:] along a chain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hamming_pairs(a: jax.Array, b: jax.Array) -> jax.Array:
    x = jax.lax.population_count(jnp.bitwise_xor(a, b))
    return jnp.sum(x.astype(jnp.int32), axis=tuple(range(1, x.ndim)))
