"""Multi-crossbar reprogramming schedules and thread balancing (§III.B–C).

Given S sections (in SWS order) and L physical crossbars programmable in
parallel, a *schedule* assigns each crossbar a chain of sections to walk:

* **stride-L** — crossbar ``i`` programs sections ``i, i+L, i+2L, …``: every
  step jumps L positions in the sorted list, so consecutive programs differ
  more (larger magnitude gap -> more bit transitions).
* **stride-1** — crossbar ``i`` is seeded at offset ``i * ceil(S/L)`` and then
  walks *consecutive* sections.  Each step reprograms between adjacent sorted
  sections; only the L seed programs are 'far'.  This is the paper's winning
  schedule (Fig. 3b, Fig. 6b).

Pricing a schedule is embarrassingly pair-parallel: every job (one crossbar
reprogram) is an independent ``popcount(prev ^ cur)``.  ``schedule_job_costs``
therefore flattens *all* chains into one batched pairs array — ``prev[i]`` /
``cur[i]`` section indices per job, with a synthetic index for the pristine
all-zero state — and prices the whole schedule in a single
``price_pairs`` call (Pallas ``hamming`` kernel on TPU, portable
``lax.population_count`` elsewhere).  Inputs may be bool planes
``[S, rows, cols]`` (packed on the fly) or canonical packed planes
``uint8[S, W, cols]`` from ``bitslice.section_planes_packed``.

Thread balancing (§III.C, Fig. 4): programming engines run in lockstep rounds
(one crossbar program per thread per round); a round lasts as long as its
most expensive job.  The paper's greedy groups *similar-cost* jobs into the
same round (sort all jobs by cost, chunk into rounds of T), which drives
``sum_r max(round_r)`` down to ~``sum(costs)/T`` — the ideal T-way speedup.
An LPT (longest-processing-time) makespan balancer is included for the
asynchronous-threads interpretation as an ablation.
"""
from __future__ import annotations

import heapq
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitslice
from repro.core import cost as cost_lib
from repro.kernels.hamming import ops as hamming_ops


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------

def stride_l_chains(s: int, l: int) -> list[np.ndarray]:
    """Chains for stride-L scheduling: chains[i] = [i, i+L, i+2L, ...].

    Chains are host numpy arrays: they encode static schedule *structure*
    (always built from concrete section counts), which keeps them usable as
    constants inside jitted pricing functions.
    """
    return [np.arange(i, s, l, dtype=np.int32) for i in range(min(l, s))]


def stride_1_chains(s: int, l: int) -> list[np.ndarray]:
    """Chains for stride-1 scheduling: L contiguous blocks of the sorted list."""
    block = math.ceil(s / l)
    chains = []
    for i in range(l):
        lo, hi = i * block, min((i + 1) * block, s)
        if lo >= hi:
            break
        chains.append(np.arange(lo, hi, dtype=np.int32))
    return chains


def make_chains(s: int, l: int, kind: str) -> list[np.ndarray]:
    if kind == "stride1":
        return stride_1_chains(s, l)
    if kind == "strideL":
        return stride_l_chains(s, l)
    raise ValueError(f"unknown schedule kind: {kind!r}")


# ---------------------------------------------------------------------------
# Batched pair pricing
# ---------------------------------------------------------------------------

def chain_pairs(
    chains: list[jnp.ndarray], *, include_initial: bool = True
) -> tuple[np.ndarray, np.ndarray]:
    """Flatten chains into one batched (prev, cur) job-index array.

    Job ``i`` reprograms a crossbar holding section ``prev[i]`` with section
    ``cur[i]``; ``prev == -1`` denotes the pristine all-zero crossbar.  Jobs
    appear chain by chain in walk order, matching the historical
    per-chain concatenation contract of :func:`schedule_job_costs`.

    Chains must be concrete (they always are: schedules are built from static
    section counts, never traced values).
    """
    prevs, curs = [], []
    for c in chains:
        c = np.asarray(c, dtype=np.int32)
        if include_initial:
            prevs.append(np.concatenate([np.array([-1], np.int32), c[:-1]]))
            curs.append(c)
        else:
            prevs.append(c[:-1])
            curs.append(c[1:])
    return np.concatenate(prevs), np.concatenate(curs)


def _as_packed(planes: jax.Array) -> jax.Array:
    """Accept bool[S, rows, cols] or packed uint8[S, W, cols] planes."""
    if planes.dtype == jnp.uint8:
        return planes
    return bitslice.pack_rows(planes)


def schedule_job_costs(
    planes: jax.Array,
    chains: list[jnp.ndarray],
    *,
    include_initial: bool = True,
) -> jax.Array:
    """Flat per-job costs (one job = one crossbar reprogram) -> int32[njobs].

    All chain steps are priced in ONE batched ``price_pairs`` call on packed
    planes — no per-chain Python loop over XORs.
    """
    packed = _as_packed(planes)
    prev, cur = chain_pairs(chains, include_initial=include_initial)
    if prev.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    # Sections are gathered as flat byte rows: a gathered [T, W, cols] block
    # would be laid out on TPU with its small minor dims padded to full tiles.
    flat = packed.reshape(packed.shape[0], -1)
    # Prepend the pristine all-zero state so prev == -1 gathers zeros.
    states = jnp.concatenate([jnp.zeros((1, flat.shape[1]), flat.dtype), flat], axis=0)
    return hamming_ops.price_pairs(states[prev + 1], states[cur + 1])


def schedule_transitions(
    planes: jax.Array,
    chains: list[jnp.ndarray],
    *,
    include_initial: bool = True,
) -> jax.Array:
    """Total transitions across all crossbars -> int32[] (sum over chains)."""
    return jnp.sum(schedule_job_costs(planes, chains, include_initial=include_initial))


def schedule_job_costs_looped(
    planes: jax.Array,
    chains: list[jnp.ndarray],
    *,
    include_initial: bool = True,
) -> jax.Array:
    """Seed reference: per-chain Python loop over bool-plane XOR sums.

    Kept as the oracle the batched packed path is parity-tested against and
    as the baseline ``benchmarks/planner_throughput.py`` measures speedup
    over (``PlannerConfig(impl="bool")``).
    """
    per_chain = [
        cost_lib.consecutive_costs(planes, c, include_initial=include_initial) for c in chains
    ]
    return jnp.concatenate(per_chain)


# ---------------------------------------------------------------------------
# Thread balancing
# ---------------------------------------------------------------------------

def lockstep_time(job_costs: jax.Array, threads: int, *, sort_jobs: bool) -> jax.Array:
    """Lockstep-rounds total time: sum over rounds of the round's max cost.

    ``sort_jobs=False`` is the unsorted baseline (jobs in arrival order, each
    round mixes small and large costs and is bottlenecked by the largest);
    ``sort_jobs=True`` is the paper's greedy similar-cost grouping.
    """
    n = job_costs.shape[0]
    if sort_jobs:
        job_costs = jnp.sort(job_costs)[::-1]
    pad = (-n) % threads
    padded = jnp.pad(job_costs, (0, pad))
    rounds = padded.reshape(-1, threads)
    return jnp.sum(jnp.max(rounds, axis=1))


def lockstep_time_host(job_costs, threads: int, *, sort_jobs: bool) -> np.int64:
    """Host int64 twin of :func:`lockstep_time` (same algorithm, same values).

    Used by the planner's packed fast path: whole-tensor totals can exceed
    int32 at extreme scale (> 2^31 transitions), which the device path —
    jax without x64 — cannot represent.  Per-job costs themselves are tiny
    (<= rows * cols bits), so int32 inputs are always safe.
    """
    costs = np.asarray(job_costs, dtype=np.int64)
    if sort_jobs:
        costs = np.sort(costs)[::-1]
    pad = (-costs.shape[0]) % threads
    if pad:
        costs = np.concatenate([costs, np.zeros(pad, np.int64)])
    rounds = costs.reshape(-1, threads)
    return np.sum(rounds.max(axis=1), dtype=np.int64) if rounds.size else np.int64(0)


def lockstep_speedup(job_costs: jax.Array, threads: int, *, sort_jobs: bool) -> jax.Array:
    """Parallel speedup vs programming all jobs sequentially on one engine."""
    seq = jnp.sum(job_costs)
    t = lockstep_time(job_costs, threads, sort_jobs=sort_jobs)
    return seq.astype(jnp.float32) / jnp.maximum(t.astype(jnp.float32), 1.0)


def lpt_assignment(
    job_costs: jax.Array,
    threads: int,
    *,
    initial_loads: np.ndarray | None = None,
    capacity: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Longest-processing-time greedy makespan balancing (async ablation).

    Returns (thread_id int32[njobs], thread_loads int64[threads]).  Runs on
    the host: the greedy is inherently sequential, and host numpy gives the
    int64 accumulators large deployments need (the former int32 ``lax.scan``
    accumulator wrapped past ~2^31 total transitions per thread; jax without
    x64 cannot widen it).  Ties break toward the lowest thread id, matching
    the previous ``argmin`` behavior.

    ``initial_loads`` seeds each thread's starting load (the crossbar pool's
    wear-leveling assignment seeds with accumulated per-crossbar wear, so
    heavy chains land on the least-worn crossbars).  ``capacity`` bounds how
    many jobs one thread may take; ``capacity=1`` turns the greedy into a
    min-max matching (each chain on a distinct physical crossbar).  Returned
    loads include the initial loads.
    """
    costs = np.asarray(job_costs, dtype=np.int64)
    if capacity is not None and costs.shape[0] > threads * capacity:
        raise ValueError(
            f"{costs.shape[0]} jobs exceed {threads} threads x capacity {capacity}"
        )
    order = np.argsort(-costs, kind="stable")
    tids = np.empty(costs.shape[0], np.int32)
    if initial_loads is None:
        loads = np.zeros(threads, np.int64)
    else:
        loads = np.asarray(initial_loads, dtype=np.int64).copy()
        if loads.shape != (threads,):
            raise ValueError(f"initial_loads shape {loads.shape} != ({threads},)")
    taken = np.zeros(threads, np.int64)
    heap = [(int(loads[t]), t) for t in range(threads)]
    heapq.heapify(heap)
    for j in order:
        while True:
            load, t = heapq.heappop(heap)
            if capacity is None or taken[t] < capacity:
                break
            # thread already full: drop it from the heap for good
        taken[t] += 1
        tids[j] = t
        loads[t] = load + int(costs[j])
        heapq.heappush(heap, (int(loads[t]), t))
    return tids, loads


def lpt_makespan(job_costs: jax.Array, threads: int) -> np.int64:
    _, loads = lpt_assignment(job_costs, threads)
    return np.max(loads)
