"""Public jit'd wrapper for the Hamming kernel (pads, dispatches).

Two entry points:
  * ``hamming_pairs``  — always routes through the Pallas kernel (compiled on
    TPU, interpreted elsewhere); the parity/testing surface.
  * ``price_pairs``    — the planner's hot-path dispatcher: the compiled
    Pallas kernel on TPU, a plain ``lax.population_count`` XOR elsewhere
    (interpret-mode Pallas runs the grid in Python and would be orders of
    magnitude slower than the portable fallback on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._util import default_interpret, on_tpu, round_up
from repro.kernels.hamming import ref as hamming_ref
from repro.kernels.hamming.kernel import hamming_pairs_kernel


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def hamming_pairs(
    a: jax.Array, b: jax.Array, *, bt: int = 1024, interpret: bool | None = None
) -> jax.Array:
    """Per-pair transition counts: popcount(a[t] ^ b[t]) -> int32[T].

    Arbitrary T: a T no larger than ``bt`` is one whole-array block, and a
    larger T runs ragged ``bt``-row blocks (the kernel drops rows past T).
    """
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch: {a.shape} vs {b.shape}")
    t = a.shape[0]
    if t == 0:
        return jnp.zeros((0,), jnp.int32)
    bt_ = t if t <= bt else round_up(bt, 32)
    return hamming_pairs_kernel(a, b, bt=bt_, interpret=default_interpret(interpret))


def chain_costs(packed_states: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Consecutive reprogram costs along a chain of packed states -> int32[S-1]."""
    return hamming_pairs(packed_states[:-1], packed_states[1:], interpret=interpret)


def price_pairs(a: jax.Array, b: jax.Array) -> jax.Array:
    """Best-available per-pair pricing: popcount(a[t] ^ b[t]) -> int32[T].

    a, b: uint8[T, W, C] packed planes (or the same sections flattened to
    uint8[T, W*C] rows).  Dispatches to the compiled Pallas
    kernel on TPU and to the portable ``lax.population_count`` oracle on every
    other backend.  Safe to call inside jit; T may be 0.
    """
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch: {a.shape} vs {b.shape}")
    if a.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    if on_tpu():
        return hamming_pairs(a, b, interpret=False)
    return hamming_ref.hamming_pairs(a, b)
