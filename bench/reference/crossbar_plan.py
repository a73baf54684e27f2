"""Plain reference of planning and programming a tensor onto a crossbar pool.

Written from the paper's description (arXiv:2410.21730) and the
configuration the cell states, in plain ``jax.numpy``, with no kernels:

- quantize: ``scale = max|w| * (1 / (2**cols - 1))`` (float32),
  ``q = round(|w| / scale)``; the crossbar holds the magnitude bits of
  ``q`` (bit 0 is the least significant column), the sign is kept digitally;
- sections: ``rows`` consecutive weights; Sorted Weight Sectioning orders
  the weights by ``|w|`` (stable) before cutting sections;
- stride-1 schedule over ``L`` crossbars: crossbar ``j`` programs the
  ``j``-th contiguous block of ``S / L`` sections, in order;
- a program of section ``t`` onto a crossbar costs one write per cell whose
  bit changes; the baseline prices the unsorted order from blank crossbars,
  the SWS figure the sorted order from what the pool holds;
- bit stucking: a cell of the lowest ``stuck_cols`` columns that would have
  to change may be left as it was; every other cell is written to its
  target.  What the crossbar then holds is what the next program starts from.

``check`` holds what the program returned against this: each tensor's
three transition counts, every achieved cell (allowed to differ from its
target only where stucking may leave it), the programmed transitions and
per-cell wear implied by the achieved cells, and the achieved weights on
the quantization grid with their source signs.  The achieved cells under
stucking are random draws of the program, so the reference reads them
from the achieved weights and checks that every one is a legal outcome.

``plan`` is the reference put in the program's place (the control):
the same pipeline with its own stucking draws, optionally on weights
rounded to a lower type first.  Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _popcount_sum(x):
    return jnp.sum(jax.lax.population_count(x), axis=-1)


@functools.partial(jax.jit, static_argnames=("rows", "cols", "crossbars", "rounding"))
def _targets(w, *, rows, cols, crossbars, rounding=None):
    flat = jnp.ravel(w).astype(jnp.float32)
    if rounding is not None:
        flat = flat.astype(rounding).astype(jnp.float32)
    levels = 2**cols - 1
    scale = jnp.maximum(jnp.max(jnp.abs(flat)), jnp.finfo(jnp.float32).tiny) * jnp.float32(1.0 / levels)
    q = jnp.clip(jnp.round(jnp.abs(flat) / scale), 0, levels).astype(jnp.int32)
    perm = jnp.argsort(jnp.abs(flat), stable=True)
    s = flat.size // rows
    shape = (crossbars, s // crossbars, rows)
    sign = jnp.where(flat < 0, -1.0, 1.0)  # a sign bit: zero is positive
    return q.reshape(shape), q[perm].reshape(shape), perm, scale, sign


def _wear(flips, cols):
    """Writes per cell [L, rows, cols] from the changed bits [L, T, rows]."""
    return jnp.stack([jnp.sum((flips >> b) & 1, axis=1) for b in range(cols)], axis=-1)


def _walk_cost(start, seq):
    """Per-crossbar cost of programming ``seq`` [L, T, rows] after ``start`` [L, rows]."""
    prev = jnp.concatenate([start[:, None], seq[:, :-1]], axis=1)
    return jnp.sum(_popcount_sum(prev ^ seq), axis=1), prev


@functools.partial(jax.jit, static_argnames=("cols", "stuck_cols"))
def _check_tensor(natural, target, perm, scale, sign, w_hat, state, *, cols, stuck_cols):
    base, _ = _walk_cost(jnp.zeros_like(state), natural)
    sws, _ = _walk_cost(state, target)
    w_hat = jnp.ravel(w_hat).astype(jnp.float32)
    q_hat = jnp.round(jnp.abs(w_hat) / scale)
    on_grid = (q_hat <= 2**cols - 1) & (w_hat == sign * q_hat * scale) | (q_hat == 0) & (w_hat == 0)
    off_grid = jnp.sum(~on_grid)
    ach = q_hat.astype(jnp.int32)[perm].reshape(target.shape)
    final, prev = _walk_cost(state, ach)
    low = (1 << stuck_cols) - 1
    violations = jnp.sum(_popcount_sum((ach ^ target) & ~(low & (prev ^ target))))
    return {"baseline": base, "sws": sws, "final": final, "violations": violations,
            "off_grid": off_grid, "wear": _wear(prev ^ ach, cols), "state": ach[:, -1]}


@functools.partial(jax.jit, static_argnames=("p_stuck", "stuck_cols"))
def _stuck_walk(target, state, key, *, p_stuck, stuck_cols):
    """Program ``target`` [L, T, rows] with stucking; returns the achieved cells."""
    low = (1 << stuck_cols) - 1
    keep = jax.random.bernoulli(key, 1.0 - p_stuck, target.shape).astype(jnp.int32) * low

    def step(cur, xs):
        tgt, kept = xs
        new = tgt ^ ((cur ^ tgt) & kept)
        return new, new

    _, ach = jax.lax.scan(step, state, (jnp.swapaxes(target, 0, 1), jnp.swapaxes(keep, 0, 1)))
    return jnp.swapaxes(ach, 0, 1)


@jax.jit
def _dequant(ach, perm, scale, sign):
    q = jnp.zeros(perm.shape, jnp.int32).at[perm].set(ach.reshape(-1))
    return q.astype(jnp.float32) * scale * sign


class Pool:
    """What each crossbar holds (``[L, rows]`` magnitudes) and its wear."""

    def __init__(self, crossbars: int, rows: int, cols: int):
        self.state = jnp.zeros((crossbars, rows), jnp.int32)
        self.wear = np.zeros((crossbars, rows, cols), np.int64)


def check(pool: Pool, w, w_hat, report: dict, planner: dict) -> dict:
    """Hold one tensor's program outputs against the reference; advances
    ``pool`` to what the achieved cells leave.  Returns the counts of
    disagreement (all 0 when the program is right)."""
    rows, cols, xb = planner["rows"], planner["cols"], planner["crossbars"]
    if w.size % (rows * xb):
        raise ValueError(f"tensor of {w.size} weights does not fill {xb} crossbars' chains evenly")
    natural, target, perm, scale, sign = _targets(w, rows=rows, cols=cols, crossbars=xb)
    got = _check_tensor(natural, target, perm, scale, sign, jnp.asarray(w_hat), pool.state,
                        cols=cols, stuck_cols=planner["stuck_cols"])
    pool.state = got["state"]
    pool.wear += np.asarray(got["wear"], np.int64)
    tot = {k: int(np.sum(np.asarray(got[k], np.int64))) for k in ("baseline", "sws", "final")}
    return {
        "baseline_diff": abs(report["transitions_baseline"] - tot["baseline"]),
        "sws_diff": abs(report["transitions_sws"] - tot["sws"]),
        "final_diff": abs(report["transitions_final"] - tot["final"]),
        "stuck_violations": int(got["violations"]),
        "off_grid": int(got["off_grid"]),
    }


def plan(pool: Pool, w, planner: dict, key, *, rounding=None) -> tuple[dict, jax.Array]:
    """The reference in the program's place: (report, achieved weights)."""
    rows, cols, xb = planner["rows"], planner["cols"], planner["crossbars"]
    natural, target, perm, scale, sign = _targets(w, rows=rows, cols=cols, crossbars=xb,
                                                  rounding=rounding)
    base, _ = _walk_cost(jnp.zeros_like(pool.state), natural)
    sws, _ = _walk_cost(pool.state, target)
    if planner["p_stuck"] < 1.0:
        ach = _stuck_walk(target, pool.state, key, p_stuck=planner["p_stuck"],
                          stuck_cols=planner["stuck_cols"])
    else:
        ach = target
    final, prev = _walk_cost(pool.state, ach)
    pool.wear += np.asarray(_wear(prev ^ ach, cols), np.int64)
    pool.state = ach[:, -1]
    report = {"transitions_baseline": int(np.sum(np.asarray(base, np.int64))),
              "transitions_sws": int(np.sum(np.asarray(sws, np.int64))),
              "transitions_final": int(np.sum(np.asarray(final, np.int64)))}
    return report, _dequant(ach, perm, scale, sign).reshape(w.shape)
