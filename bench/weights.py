"""Seeded weights on the crossbar quantization grid, made on the device.

Every matrix the planner deploys is drawn directly on the default
``CrossbarSpec`` grid (10 magnitude bits, sign-magnitude): bell-shaped
magnitudes ``q`` in [0, 1023] at the fan-in scale of the model's own
initializer, a power-of-two step ``s0``, and ``q = 1023`` at element 0 of
layer 0, so that the tensor's largest magnitude is exactly ``1023 * s0``.  The planner's quantizer then recovers ``s0`` and ``q``
exactly, and at full reprogramming (``p_stuck = 1``) deploys the tensor
unchanged: the weights a serving cell serves are the weights a planned
deployment of them would serve.

The same functions give the plain references their weights, one layer at a
time, from the seed alone; integer arithmetic makes them bit-identical to
the served ones however they are compiled.  Nothing here imports the program.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

COLS = 10
LEVELS = 2**COLS - 1


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


BELL_STD = 147.8  # std of a sum of four uniform bytes: sqrt(4 * (256**2 - 1) / 12)


def _bell(key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Bell-shaped integers in [-510, 510]: the sum of the four bytes of a
    random uint32, centred.  Integer arithmetic, so every program that makes
    them -- in one jitted call or one slice at a time -- gets the same bits."""
    b = jax.random.bits(key, shape, jnp.uint32)
    s = (b & 255) + ((b >> 8) & 255) + ((b >> 16) & 255) + (b >> 24)
    return s.astype(jnp.int32) - 510


def grid_step(fan_in: int) -> float:
    """Power-of-two grid step nearest to a ``1/sqrt(fan_in)`` weight std for
    magnitudes ``2 * |bell|`` (the model initializer's fan-in scale)."""
    return 2.0 ** round(math.log2(1.0 / math.sqrt(fan_in) / (2 * BELL_STD)))


def grid_slice(key: jax.Array, layer, shape: tuple[int, ...], *, gain: bool = False):
    """(q int32, sign int32) of one [..., K, N] (or [N] gain) slice of a grid tensor.

    ``layer`` may be traced.  Gains sit at the top level (q = 1023, weight
    ~1); matrices take ``q = 2 * |bell|`` (0..1020) with the bell's sign, and
    layer 0 holds ``q = 1023`` at element 0.  Zero magnitudes carry sign +1,
    as the quantizer gives them.
    """
    if gain:
        return jnp.full(shape, LEVELS, jnp.int32), jnp.ones(shape, jnp.int32)
    z = _bell(jax.random.fold_in(key, layer), shape)
    q = 2 * jnp.abs(z)
    first = (0,) * len(shape)
    q = q.at[first].set(jnp.where(layer == 0, LEVELS, q[first]))
    sign = jnp.where(z < 0, -1, 1).astype(jnp.int32)
    return q, sign


def grid_weight(q: jax.Array, sign: jax.Array, step: float) -> jax.Array:
    """float32 grid value ``sign * q * step`` (exact)."""
    return (sign * q).astype(jnp.float32) * jnp.float32(step)


EMBED_STEP = 2.0**-13  # bell * 2**-13: std 0.018, near the initializer's 0.02


def dense_slice(key: jax.Array, shape: tuple[int, ...], *, gain: bool = False) -> jax.Array:
    """A tensor the planner does not deploy: embedding rows (bell-shaped,
    std ~0.02 as the model's initializer draws them) or a norm gain (ones)."""
    if gain:
        return jnp.ones(shape, jnp.float32)
    return _bell(key, shape).astype(jnp.float32) * jnp.float32(EMBED_STEP)


# ---------------------------------------------------------------------------
# Layout of a parameter tree, and the rule for what is on the grid
# ---------------------------------------------------------------------------

MIN_GRID_SIZE = 4096  # tensors smaller than this are not deployed (planner default)


def layout(leaves: list[tuple[str, tuple[int, ...], bool]]) -> list[dict]:
    """Every leaf of a parameter tree, with what the planner makes of it.

    ``leaves`` holds ``(name, shape, stacked)`` for each leaf, as a
    configuration's reference declares them (``bench/reference/<name>.py``
    ``leaves``); a stacked leaf's first axis is the layer, and its slice
    (one layer's share) may keep further leading axes, such as experts.
    A leaf is on the grid when the planner would deploy it: at least 2-D,
    at least ``MIN_GRID_SIZE`` elements, and not the embedding table.  A
    name ending in ``/g`` is a norm gain.  A grid matrix's step follows its
    fan-in, the second-to-last axis of its slice (the contraction axis K of
    ``[..., K, N]``).
    """
    out = []
    for name, shape, stacked in leaves:
        shape = tuple(shape)
        size = math.prod(shape)
        gain = name.endswith("/g")
        grid = len(shape) >= 2 and size >= MIN_GRID_SIZE and "embed" not in name
        slice_shape = shape[1:] if stacked else shape
        step = 2.0**-10
        if grid and not gain:
            step = grid_step(slice_shape[-2] if len(slice_shape) >= 2 else slice_shape[0])
        out.append({
            "name": name, "shape": shape, "stacked": stacked, "gain": gain, "grid": grid,
            "slice": slice_shape, "step": step,
        })
    return out


def leaf_slice(key: jax.Array, leaf: dict, layer) -> jax.Array:
    """float32 value of one layer's slice of ``leaf`` (the whole leaf when
    it is not layer-stacked), drawn in one call whatever leading axes the
    slice keeps: a grid tensor holds ``q = 1023`` at its element 0 of layer
    0 only, so its largest magnitude is ``1023 * step`` across all of them."""
    k = leaf_key(key, leaf["name"])
    if leaf["grid"]:
        q, sign = grid_slice(k, layer, leaf["slice"], gain=leaf["gain"])
        return grid_weight(q, sign, leaf["step"])
    return dense_slice(k, leaf["slice"], gain=leaf["gain"])
