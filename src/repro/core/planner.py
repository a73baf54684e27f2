"""Deployment planner: DNN params -> crossbar programming plan + cost report.

This is the integration point that makes the paper's technique a first-class
framework feature: ``build_deployment`` consumes any pytree of weights (all
matmul weights of the assigned LM architectures), quantizes and bit-slices
them, applies Sorted Weight Sectioning, chooses a multi-crossbar schedule,
prices the reprogramming workload against the unsorted ISAAC/CASCADE-style
baseline, applies bit stucking, and returns both the metrics and the
*achieved* (error-injected) weights for accuracy evaluation.

Embedding-style lookup tables are excluded (CIM crossbars compute dot
products; lookups never map to them — DESIGN.md §4); callers control this
via ``PlannerConfig.exclude`` name patterns and ``min_size``/``min_ndim``.

Internal invariant: every tensor is handled as a *padded flat vector* of
length ``S * rows`` together with ``perm_full`` — a permutation of
``range(S * rows)`` mapping crossbar slot -> source element (source indices
``>= n`` are zero padding).  All orderings (magnitude sort, beyond-paper TSP
section reorder) compose into ``perm_full``, and reconstruction is a single
scatter, so index matching stays exact no matter how sections are shuffled.

**Fast path (default, ``impl="packed"``).**  The whole per-tensor pipeline is
one jitted function keyed on ``(tensor shape, spec, config)``: pricing a full
LM config retraces once per *distinct* weight shape (a handful for a
transformer), not once per tensor.  Bit planes are packed exactly once into
the canonical ``uint8[S, W, cols]`` words (``bitslice.section_planes_packed``)
and every downstream consumer — the batched pair pricing in
``core.schedule``, the stucking walks in ``core.stucking``, the TSP section
reorder in ``core.sws`` — operates on packed words; bool planes are only
unpacked at the very end to reconstruct achieved weights.  Pair pricing
dispatches through ``repro.kernels.hamming.ops.price_pairs``: the compiled
Pallas ``hamming`` kernel on TPU, a portable ``lax.population_count`` XOR on
CPU/GPU.  ``impl="bool"`` preserves the original eager bool-plane pipeline
(per-chain Python loops) as the parity oracle and benchmark baseline; both
paths share one PRNG discipline and produce bit-identical plans.
"""
from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitslice, planes, schedule, stucking, sws

if TYPE_CHECKING:
    from repro.core.pool import CrossbarPool


@dataclasses.dataclass(frozen=True)
class CrossbarSpec:
    """Geometry + encoding of the physical crossbars (paper default 128x10)."""

    rows: int = 128
    cols: int = 10
    encoding: str = "sign_magnitude"


@dataclasses.dataclass(frozen=True)
class PlannerConfig:
    sws: bool = True
    schedule: str = "stride1"  # "stride1" | "strideL"
    crossbars: int = 16  # L physical crossbars programmed in parallel
    threads: int = 64  # T lockstep programming engines (Fig. 7)
    p_stuck: float = 1.0  # 1.0 = full reprogramming (no stucking)
    stuck_cols: int = 1
    include_initial: bool = True
    section_order: str = "magnitude"  # "magnitude" | "tsp" (beyond-paper)
    min_size: int = 4096
    min_ndim: int = 2
    exclude: tuple[str, ...] = ("embed", "embedding", "lm_head", "pos_emb")
    seed: int = 0
    impl: str = "packed"  # "packed" (jitted fast path) | "bool" (reference)
    # chain->crossbar leveling when streaming through a CrossbarPool:
    # "none" | "rotate" | "lpt" | "fault" (fault-aware remap, core/nonideal);
    # None defers to the pool's own setting
    pool_leveling: str | None = None
    # stored-plane codec (core/planes.py): "raw" | "const_rle" | "col_perm" |
    # "col_perm_rle".  Non-raw codecs change the *physical* bits the
    # crossbars hold (and hence the priced transitions); logical planes —
    # and the deployed w_hat — decode back byte-identically.
    codec: str = "raw"


@dataclasses.dataclass
class TensorReport:
    name: str
    shape: tuple[int, ...]
    n_weights: int
    n_sections: int
    transitions_baseline: int  # unsorted order, full reprogramming
    transitions_sws: int  # SWS order, full reprogramming
    transitions_final: int  # SWS order + bit stucking at p
    lockstep_time_unsorted: int
    lockstep_time_greedy: int
    lockstep_time_ideal: float
    quant_mse: float  # ||w - w_hat||^2 / n  (quantization + stucking error)
    # dequantization constants of the achieved weights — what deploy_params
    # needs to re-materialize crossbar operands (packed / int8 planes) from
    # the dense w_hat without re-running the planner
    scale: float = 0.0
    offset: float = 0.0

    @property
    def sws_speedup(self) -> float:
        return self.transitions_baseline / max(self.transitions_sws, 1)

    @property
    def total_speedup(self) -> float:
        return self.transitions_baseline / max(self.transitions_final, 1)


@dataclasses.dataclass
class DeploymentPlan:
    spec: CrossbarSpec
    config: PlannerConfig
    reports: dict[str, TensorReport]
    # name -> achieved weights (w_hat), held in host memory: the device
    # copies are the serving operands deploy_params materializes, and dense
    # f32 copies of every deployed tensor beside the planner's working set
    # would not fit one chip for a full-width LM
    deployed: dict[str, np.ndarray]
    pool_stats: dict | None = None  # wear summary when built against a CrossbarPool

    def totals(self) -> dict[str, float]:
        base = sum(r.transitions_baseline for r in self.reports.values())
        sws_t = sum(r.transitions_sws for r in self.reports.values())
        fin = sum(r.transitions_final for r in self.reports.values())
        lk_u = sum(r.lockstep_time_unsorted for r in self.reports.values())
        lk_g = sum(r.lockstep_time_greedy for r in self.reports.values())
        lk_i = sum(r.lockstep_time_ideal for r in self.reports.values())
        return {
            "transitions_baseline": base,
            "transitions_sws": sws_t,
            "transitions_final": fin,
            "sws_speedup": base / max(sws_t, 1),
            "total_speedup": base / max(fin, 1),
            "lockstep_speedup_unsorted": base / lk_u if lk_u else float("nan"),
            "lockstep_speedup_greedy": sws_t / lk_g if lk_g else float("nan"),
            "lockstep_time_ideal": lk_i,
        }


def _sort_key(flat_padded: jax.Array, encoding: str) -> jax.Array:
    # sign_magnitude stores |w|: sort by magnitude so bit patterns sort too.
    # offset_binary stores w - min: sort by value for the same property.
    return jnp.abs(flat_padded) if encoding == "sign_magnitude" else flat_padded


def _perm_full_with_inverse(
    flat_padded: jax.Array, spec: CrossbarSpec, config: PlannerConfig, q_padded: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Slot -> source permutation of length S*rows, plus its inverse.

    The inverse comes for free from the host-side sort on CPU
    (``sws.stable_argsort``), letting reconstruction be a gather instead of
    a (much slower) scatter.
    """
    total = flat_padded.shape[0]
    if not config.sws:
        ar = jnp.arange(total, dtype=jnp.int32)
        return ar, ar
    perm, inv = sws.stable_argsort(
        _sort_key(flat_padded, spec.encoding),
        with_inverse=True,
        nonneg=spec.encoding == "sign_magnitude",  # key is |w|
    )
    if config.section_order == "tsp":
        packed = bitslice.section_planes_packed(q_padded[perm], spec.rows, spec.cols)
        order = sws.tsp_greedy_order(packed)
        slot = (order[:, None] * spec.rows + jnp.arange(spec.rows, dtype=jnp.int32)).reshape(-1)
        perm = perm[slot]
        inv = sws.inverse_permutation(perm)
    return perm, inv


def _perm_full(
    flat_padded: jax.Array, spec: CrossbarSpec, config: PlannerConfig, q_padded: jax.Array
) -> jax.Array:
    """Slot -> source-element permutation of length S*rows (see module doc)."""
    return _perm_full_with_inverse(flat_padded, spec, config, q_padded)[0]


def _perm_full_bool(
    flat_padded: jax.Array, spec: CrossbarSpec, config: PlannerConfig, q_padded: jax.Array
) -> jax.Array:
    """Eager twin of :func:`_perm_full` for the bool reference paths.

    Uses the seed device argsort — stable, hence the identical permutation to
    the host-callback sort of the packed path.  Kept as the ONE place the
    bool pipeline's sort discipline lives (the stateless reference and the
    pool twin both call it), so the packed/bool parity contract cannot drift
    between copies.
    """
    total = flat_padded.shape[0]
    if not config.sws:
        return jnp.arange(total, dtype=jnp.int32)
    perm = jnp.argsort(_sort_key(flat_padded, spec.encoding), stable=True).astype(jnp.int32)
    if config.section_order == "tsp":
        packed_t = bitslice.section_planes_packed(q_padded[perm], spec.rows, spec.cols)
        order = sws.tsp_greedy_order(packed_t)
        slot = (
            order[:, None] * spec.rows + jnp.arange(spec.rows, dtype=jnp.int32)
        ).reshape(-1)
        perm = perm[slot]
    return perm


@partial(jax.jit, static_argnames=("spec", "config"))
def _prep_core_pool(
    flat: jax.Array, spec: CrossbarSpec, config: PlannerConfig
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Shared per-tensor prep: quantize, baseline pricing, SWS packed planes.

    The common prefix of the stateless ``_analyze_core`` (which inlines it
    under its own jit) and of pool-mode analysis, where the stateful
    ``CrossbarPool`` performs the pricing walk itself — it must carry
    crossbar content and wear across tensors, so the jit stops at the
    canonical SWS-ordered packed planes plus the pristine-baseline job costs
    and the reconstruction aux.  Same shape-bucketed retrace behavior as
    ``_analyze_core``.
    """
    n = flat.shape[0]
    pad = (-n) % spec.rows
    flat_padded = jnp.pad(flat, (0, pad))
    total = n + pad
    s = total // spec.rows
    l = max(1, min(config.crossbars, s))

    with jax.named_scope("plan.quantize"):
        qt = bitslice.quantize(flat, spec.cols, spec.encoding)
        q_padded = jnp.pad(qt.q, (0, pad))
        sign_padded = jnp.pad(qt.sign, (0, pad), constant_values=1)

    chains = schedule.make_chains(s, l, config.schedule)

    # --- baseline: unsorted natural order, full reprogramming --------------
    with jax.named_scope("plan.price_baseline"):
        packed_u = bitslice.section_planes_packed(q_padded, spec.rows, spec.cols)
        jobs_u = schedule.schedule_job_costs(
            packed_u, chains, include_initial=config.include_initial
        )

    # --- SWS order ---------------------------------------------------------
    with jax.named_scope("plan.sws_sort"):
        perm, inv_perm = _perm_full_with_inverse(flat_padded, spec, config, q_padded)
    with jax.named_scope("plan.pack"):
        packed_s = bitslice.section_planes_packed(q_padded[perm], spec.rows, spec.cols)
        sign_slots = sign_padded[perm].reshape(s, spec.rows)
    aux = {
        "packed_s": packed_s,
        "sign_slots": sign_slots,
        "scale": qt.scale,
        "offset": qt.offset,
        "inv_perm": inv_perm,
    }
    return jobs_u, aux


@partial(jax.jit, static_argnames=("spec", "config"))
def _analyze_core(
    flat: jax.Array, key: jax.Array, spec: CrossbarSpec, config: PlannerConfig
) -> tuple[dict[str, jax.Array], jax.Array]:
    """Jitted per-tensor pipeline on canonical packed planes.

    flat: f32[n] logical weights.  Retraces per distinct ``n`` (and static
    spec/config), so same-shape tensors across a model share one compilation.
    Returns (metric scalars, reconstruction aux).  Weight reconstruction
    happens *outside* this jit (see ``analyze_tensor``): XLA contracts the
    dequant multiply+add into an FMA inside a fused graph, which would break
    bit-exactness of w_hat against the eager bool reference.
    """
    jobs_u, prep = _prep_core_pool(flat, spec, config)
    packed_s = prep["packed_s"]
    s = packed_s.shape[0]
    l = max(1, min(config.crossbars, s))
    chains = schedule.make_chains(s, l, config.schedule)
    jobs_s = schedule.schedule_job_costs(packed_s, chains, include_initial=config.include_initial)

    # --- bit stucking on the SWS schedule ----------------------------------
    # Totals, lockstep times, and lockstep_time_ideal are all aggregated on
    # the host (int64 / float64) in the wrapper: device sums are int32-bound
    # (jax without x64) and a whole-tensor total can exceed 2^31 at extreme
    # scale, while per-job and per-chain values stay far below it.
    if config.p_stuck < 1.0:
        stuck_chain_totals, achieved_packed = stucking.stuck_schedule_packed(
            packed_s,
            chains,
            config.p_stuck,
            key,
            rows=spec.rows,
            stuck_cols=config.stuck_cols,
            include_initial=config.include_initial,
        )
    else:
        stuck_chain_totals = None
        achieved_packed = packed_s

    metrics = {
        "jobs_u": jobs_u,
        "jobs_s": jobs_s,
        "stuck_chain_totals": stuck_chain_totals,
    }
    aux = {
        "achieved_packed": achieved_packed,
        "sign_slots": prep["sign_slots"],
        "scale": prep["scale"],
        "offset": prep["offset"],
        "inv_perm": prep["inv_perm"],
    }
    return metrics, aux


@partial(jax.jit, static_argnames=("rows",))
def _dequant_slots(
    achieved_packed: jax.Array,
    sign_slots: jax.Array,
    scale: jax.Array,
    offset: jax.Array,
    *,
    rows: int,
) -> jax.Array:
    """Achieved packed planes -> achieved slot weights f32[S, rows].

    Deliberately its own jit entry, called identically by the packed and bool
    planner impls: float rounding (XLA may contract the dequant multiply+add
    into an FMA) is then decided by ONE executable, so both impls get
    bit-identical weights by construction.
    """
    achieved = bitslice.unpack_rows(achieved_packed, rows)
    return bitslice.dequantize_from_planes(achieved, sign_slots, scale, offset)


def _prep_bool(
    flat: jax.Array, spec: CrossbarSpec, config: PlannerConfig
) -> tuple[Any, jax.Array, jax.Array, list[np.ndarray], jax.Array, jax.Array]:
    """Eager twin of :func:`_prep_core_pool`: the seed reference's per-tensor
    prep — quantize, pad, baseline job pricing, SWS permutation.  The ONE
    place the bool pipeline's prep discipline lives; shared by the stateless
    reference and the pool twin so the packed/bool parity contract cannot
    drift between copies.

    Returns (qt, q_padded, sign_padded, chains, jobs_u, perm).
    """
    n = flat.shape[0]
    pad = (-n) % spec.rows
    flat_padded = jnp.pad(flat, (0, pad))
    s = flat_padded.shape[0] // spec.rows
    l = max(1, min(config.crossbars, s))

    qt = bitslice.quantize(flat, spec.cols, spec.encoding)
    q_padded = jnp.pad(qt.q, (0, pad))
    sign_padded = jnp.pad(qt.sign, (0, pad), constant_values=1)

    # --- baseline: unsorted natural order, full reprogramming --------------
    planes_u = bitslice.bitplanes(q_padded.reshape(s, spec.rows), spec.cols)
    chains = schedule.make_chains(s, l, config.schedule)
    jobs_u = schedule.schedule_job_costs_looped(
        planes_u, chains, include_initial=config.include_initial
    )

    # --- SWS order (see _perm_full_bool: the seed device argsort, identical
    # to the fast host-callback sort the packed path uses) ------------------
    perm = _perm_full_bool(flat_padded, spec, config, q_padded)
    return qt, q_padded, sign_padded, chains, jobs_u, perm


def _analyze_tensor_bool(
    w: jax.Array,
    spec: CrossbarSpec,
    config: PlannerConfig,
    key: jax.Array,
    name: str = "w",
) -> tuple[TensorReport, jax.Array]:
    """Seed reference pipeline: eager bool planes + per-chain loops.

    Bit-identical to the packed path (same PRNG discipline); kept for parity
    tests and as the ``benchmarks/planner_throughput.py`` baseline.
    """
    flat = jnp.ravel(w).astype(jnp.float32)
    n = flat.shape[0]
    qt, q_padded, sign_padded, chains, jobs_u, perm = _prep_bool(flat, spec, config)
    total = q_padded.shape[0]
    s = total // spec.rows
    trans_base = int(jnp.sum(jobs_u))
    lk_unsorted = int(schedule.lockstep_time(jobs_u, config.threads, sort_jobs=False))

    planes_s = bitslice.bitplanes(q_padded[perm].reshape(s, spec.rows), spec.cols)
    jobs_s = schedule.schedule_job_costs_looped(
        planes_s, chains, include_initial=config.include_initial
    )
    trans_sws = int(jnp.sum(jobs_s))
    lk_greedy = int(schedule.lockstep_time(jobs_s, config.threads, sort_jobs=True))
    lk_ideal = float(jnp.sum(jobs_s)) / config.threads

    # --- bit stucking on the SWS schedule ----------------------------------
    if config.p_stuck < 1.0:
        total_fin, achieved = stucking.stuck_schedule(
            planes_s,
            chains,
            config.p_stuck,
            key,
            stuck_cols=config.stuck_cols,
            include_initial=config.include_initial,
        )
        trans_final = int(total_fin)
    else:
        trans_final = trans_sws
        achieved = planes_s

    # --- reconstruct achieved weights (exact index matching) ---------------
    sign_slots = sign_padded[perm].reshape(s, spec.rows)
    w_hat_slots = _dequant_slots(
        bitslice.pack_rows(achieved), sign_slots, qt.scale, qt.offset, rows=spec.rows
    )
    logical = jnp.zeros((total,), dtype=jnp.float32).at[perm].set(w_hat_slots.reshape(-1))
    w_hat_flat = logical[:n]
    w_hat = w_hat_flat.reshape(w.shape).astype(w.dtype)

    report = TensorReport(
        name=name,
        shape=tuple(w.shape),
        n_weights=int(n),
        n_sections=int(s),
        transitions_baseline=trans_base,
        transitions_sws=trans_sws,
        transitions_final=trans_final,
        lockstep_time_unsorted=lk_unsorted,
        lockstep_time_greedy=lk_greedy,
        lockstep_time_ideal=lk_ideal,
        quant_mse=float(jnp.mean((flat - w_hat_flat) ** 2)),
        scale=float(qt.scale),
        offset=float(qt.offset),
    )
    return report, w_hat


def _analyze_tensor_pool(
    w: jax.Array,
    spec: CrossbarSpec,
    config: PlannerConfig,
    key: jax.Array,
    pool: "CrossbarPool",
    name: str = "w",
) -> tuple[TensorReport, jax.Array]:
    """Per-tensor pipeline streaming through a persistent ``CrossbarPool``.

    ``transitions_sws``/``transitions_final`` price reprogramming from the
    pool's *current* content (the first job of every chain is a cross-tensor
    seam); with the pool reset between tensors they reproduce the stateless
    path bit-exactly (parity invariant pinned by ``tests/test_pool.py``).
    Supports both planner impls: ``packed`` preps via a jitted core,
    ``bool`` via the eager seed path; the pool twins mirror the same split.
    """
    if not config.include_initial:
        raise ValueError(
            "pool streaming prices physical seam programs; include_initial=False "
            "has no pool interpretation"
        )
    if (spec.rows, spec.cols) != (pool.spec.rows, pool.spec.cols):
        raise ValueError(f"planner spec {spec} != pool spec {pool.spec}")
    flat = jnp.ravel(w).astype(jnp.float32)
    n = int(flat.shape[0])
    s = -(-n // spec.rows)
    l = max(1, min(config.crossbars, s))
    chains = schedule.make_chains(s, l, config.schedule)

    with jax.profiler.TraceAnnotation("plan.prep"):
        if config.impl == "packed":
            jobs_u, aux = _prep_core_pool(flat, spec, config)
        elif config.impl == "bool":
            qt, q_padded, sign_padded, chains, jobs_u, perm = _prep_bool(flat, spec, config)
            aux = {
                "packed_s": bitslice.section_planes_packed(q_padded[perm], spec.rows, spec.cols),
                "sign_slots": sign_padded[perm].reshape(s, spec.rows),
                "scale": qt.scale,
                "offset": qt.offset,
                "inv_perm": sws.inverse_permutation(perm),
            }
        else:
            raise ValueError(f"unknown planner impl: {config.impl!r}")

    # codec layer: the pool programs/prices/wears the *stored* bits
    # (planes.PlaneSet.physical — permuted columns, reconstructed constants),
    # so transitions under a codec are the physical transitions its layout
    # actually costs.  The bool impl stays raw-only: it is the parity oracle
    # for the packed pipeline, and codec encoding happens on packed words.
    pset = None
    if config.codec != "raw":
        if config.impl == "bool":
            raise ValueError("plane codecs require impl='packed' (bool is the raw parity oracle)")
        # under bit stucking the stored lowest-order columns are deliberately
        # under-programmed; pin them so the bounded LSB error stays an LSB
        # error (plan_col_order docstring)
        pin = config.stuck_cols if config.p_stuck < 1.0 else 0
        pset = planes.encode(
            aux["packed_s"], config.codec, chains=chains, pin_cols=pin
        )

    prep = pool.program(
        pset if pset is not None else aux["packed_s"],
        chains,
        p_stuck=config.p_stuck,
        key=key,
        stuck_cols=config.stuck_cols,
        leveling=config.pool_leveling,
        impl=config.impl,
        name=name,
    )

    # dequantize what the array *reads back* (== prep.achieved byte-for-byte
    # unless the pool has injected faults — core/nonideal.py), so deployed
    # weights and everything served from them see the non-ideal cells.
    # Under a codec the readback is in the stored layout: fault masks have
    # already applied to the physical bits, and logical planes are recovered
    # *after* the read (planes.logical_from_physical), mirroring hardware.
    with jax.profiler.TraceAnnotation("plan.dequant"):
        achieved_read = prep.achieved_read
        if pset is not None:
            achieved_read = planes.logical_from_physical(achieved_read, pset.col_order)
        w_hat_slots = _dequant_slots(
            achieved_read, aux["sign_slots"], aux["scale"], aux["offset"], rows=spec.rows
        )
        w_hat_flat = w_hat_slots.reshape(-1)[aux["inv_perm"]][:n]
        w_hat = w_hat_flat.reshape(w.shape).astype(w.dtype)

    if pool.integrity is not None:
        # the reconstruction closure core/integrity.py needs to dequantize
        # repaired planes back into served weights, bit-exactly (rebuild)
        pool.integrity.attach_aux(name, {
            "sign_slots": aux["sign_slots"],
            "scale": aux["scale"],
            "offset": aux["offset"],
            "inv_perm": aux["inv_perm"],
            "n": n,
            "shape": tuple(w.shape),
            "dtype": w.dtype,
        })

    with jax.profiler.TraceAnnotation("plan.report"):
        jobs_u_np = np.asarray(jobs_u)
        report = TensorReport(
            name=name,
            shape=tuple(w.shape),
            n_weights=n,
            n_sections=s,
            transitions_baseline=int(np.sum(jobs_u_np, dtype=np.int64)),
            transitions_sws=prep.transitions_full,
            transitions_final=prep.transitions_programmed,
            lockstep_time_unsorted=int(
                schedule.lockstep_time_host(jobs_u_np, config.threads, sort_jobs=False)
            ),
            lockstep_time_greedy=int(
                schedule.lockstep_time_host(prep.job_costs, config.threads, sort_jobs=True)
            ),
            lockstep_time_ideal=float(prep.transitions_full) / config.threads,
            quant_mse=float(jnp.mean((flat - w_hat_flat) ** 2)),
            scale=float(aux["scale"]),
            offset=float(aux["offset"]),
        )
    return report, w_hat


def analyze_tensor(
    w: jax.Array,
    spec: CrossbarSpec,
    config: PlannerConfig,
    key: jax.Array,
    name: str = "w",
    *,
    pool: "CrossbarPool | None" = None,
) -> tuple[TensorReport, jax.Array]:
    """Full paper pipeline for one weight tensor.

    Returns (report, w_hat) where w_hat carries the achieved (quantized +
    stuck-bit) values in the tensor's logical layout.  With ``pool`` the
    tensor streams through persistent crossbar state instead of a pristine
    per-tensor pool (see ``core.pool``).
    """
    if config.codec not in planes.CODECS:
        raise ValueError(
            f"unknown plane codec {config.codec!r}; choose from {planes.CODECS}"
        )
    if pool is not None:
        return _analyze_tensor_pool(w, spec, config, key, pool, name=name)
    if config.codec != "raw":
        # Codec pricing is inherently a physical-programming question, so the
        # stateless path routes through an ephemeral pristine pool: streaming
        # a tensor into an all-zero pool reproduces stateless per-tensor
        # accounting bit-exactly (pool parity invariant (a), tests/test_pool.py).
        from repro.core.pool import CrossbarPool

        eph = CrossbarPool(spec, max(1, config.crossbars))
        return _analyze_tensor_pool(w, spec, config, key, eph, name=name)
    if config.impl == "bool":
        return _analyze_tensor_bool(w, spec, config, key, name=name)
    if config.impl != "packed":
        raise ValueError(f"unknown planner impl: {config.impl!r}")

    flat = jnp.ravel(w).astype(jnp.float32)
    metrics, aux = _analyze_core(flat, key, spec, config)

    # Reconstruction runs through the SAME _dequant_slots executable as the
    # bool reference, so float rounding matches it bit-for-bit; the gather by
    # the host-computed inverse permutation replaces the reference's scatter
    # (pure data movement either way — values are bit-identical).
    w_hat_slots = _dequant_slots(
        aux["achieved_packed"], aux["sign_slots"], aux["scale"], aux["offset"],
        rows=spec.rows,
    )
    n = flat.shape[0]
    w_hat_flat = w_hat_slots.reshape(-1)[aux["inv_perm"]][:n]
    w_hat = w_hat_flat.reshape(w.shape).astype(w.dtype)

    # Host int64 aggregation: whole-tensor totals can exceed int32 at
    # extreme scale (see _analyze_core).  Matches the bool reference's
    # values exactly wherever the reference itself does not overflow.
    jobs_u = np.asarray(metrics["jobs_u"])
    jobs_s = np.asarray(metrics["jobs_s"])
    trans_sws = int(np.sum(jobs_s, dtype=np.int64))
    if metrics["stuck_chain_totals"] is not None:
        trans_final = int(np.sum(np.asarray(metrics["stuck_chain_totals"]), dtype=np.int64))
    else:
        trans_final = trans_sws

    report = TensorReport(
        name=name,
        shape=tuple(w.shape),
        n_weights=int(flat.shape[0]),
        n_sections=-(-int(flat.shape[0]) // spec.rows),
        transitions_baseline=int(np.sum(jobs_u, dtype=np.int64)),
        transitions_sws=trans_sws,
        transitions_final=trans_final,
        lockstep_time_unsorted=int(
            schedule.lockstep_time_host(jobs_u, config.threads, sort_jobs=False)
        ),
        lockstep_time_greedy=int(
            schedule.lockstep_time_host(jobs_s, config.threads, sort_jobs=True)
        ),
        lockstep_time_ideal=float(trans_sws) / config.threads,
        quant_mse=float(jnp.mean((flat - w_hat_flat) ** 2)),
        scale=float(aux["scale"]),
        offset=float(aux["offset"]),
    )
    return report, w_hat


def iter_weights(params: Any, config: PlannerConfig):
    """Yield (name, tensor) for every crossbar-eligible weight in a pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    # exclude patterns are literal substrings: escape them so metacharacters
    # ("w.bias", "head[") neither over-match nor blow up the alternation
    pat = (
        re.compile("|".join(re.escape(p) for p in config.exclude))
        if config.exclude
        else None
    )
    for path, leaf in flat:
        if not hasattr(leaf, "ndim"):
            continue
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if leaf.ndim < config.min_ndim or leaf.size < config.min_size:
            continue
        if pat is not None and pat.search(name.lower()):
            continue
        yield name, leaf


def _compile_prep_sizes(params: Any, spec: CrossbarSpec, config: PlannerConfig) -> None:
    """Compile the pool path's per-size prep program for every distinct
    tensor size at once, in threads, before tensors stream one by one.

    The program holds a stable sort over the whole tensor, which the TPU
    compiler takes most of a minute to build for each size, while the
    compiler releases the GIL; a full-width LM has a handful of distinct
    sizes.  The streaming loop's calls then find these executables in
    JAX's compile cache.
    """
    sizes = sorted({int(np.prod(w.shape)) for _, w in iter_weights(params, config)})
    if len(sizes) < 2:
        return

    def compile_one(n: int) -> None:
        with jax.profiler.TraceAnnotation("plan.compile_prep.size"):
            _prep_core_pool.lower(
                jax.ShapeDtypeStruct((n,), jnp.float32), spec, config
            ).compile()

    from concurrent.futures import ThreadPoolExecutor

    with jax.profiler.TraceAnnotation("plan.compile_prep", sizes=len(sizes)):
        with ThreadPoolExecutor(len(sizes)) as ex:
            list(ex.map(compile_one, sizes))


def build_deployment(
    params: Any,
    spec: CrossbarSpec = CrossbarSpec(),
    config: PlannerConfig = PlannerConfig(),
    *,
    progress: Callable[[str], None] | None = None,
    pool: "CrossbarPool | None" = None,
) -> DeploymentPlan:
    """Plan crossbar deployment for every eligible weight in ``params``.

    With ``pool``, the model's tensors stream through ONE persistent
    crossbar pool in iteration order: every tensor's chains reprogram
    whatever the previous tensor left on its assigned crossbars (cross-tensor
    seams), and the pool's per-cell wear counters accumulate the whole
    deployment.  The per-tensor PRNG split discipline is identical with and
    without a pool, so resetting the pool between tensors recovers the
    stateless plan bit-exactly.

    Profiler spans (``plan.*``, ``pool.*``; idle unless a trace is on) mark
    the pass, the prep compiles, and each tensor's phases.
    """
    with jax.profiler.TraceAnnotation("plan.deployment"):
        if pool is not None and config.impl == "packed":
            _compile_prep_sizes(params, spec, config)
        key = jax.random.PRNGKey(config.seed)
        reports: dict[str, TensorReport] = {}
        deployed: dict[str, np.ndarray] = {}
        for name, w in iter_weights(params, config):
            key, sub = jax.random.split(key)
            if progress:
                progress(name)
            n = int(np.prod(w.shape))
            with jax.profiler.TraceAnnotation(
                "plan.tensor", n_weights=n, sections=-(-n // spec.rows)
            ):
                report, w_hat = analyze_tensor(w, spec, config, sub, name=name, pool=pool)
                reports[name] = report
                with jax.profiler.TraceAnnotation("plan.deployed.readback"):
                    deployed[name] = np.asarray(w_hat)
        return DeploymentPlan(
            spec=spec,
            config=config,
            reports=reports,
            deployed=deployed,
            pool_stats=pool.stats().to_dict() if pool is not None else None,
        )


MATERIALIZATIONS = ("dense", "packed", "planes_int8")

# Deployed tensors whose consumers are not plain [K, N] matmuls (per-head
# reshapes, convolutions, elementwise/einsum uses): always materialized as
# dense w_hat even under "packed"/"planes_int8" — still the achieved
# crossbar weights, just dense-served.  Matched against '/'-separated path
# components of the tensor name, not substrings.
MATERIALIZE_DENSE_ONLY = (
    "wk_b", "wv_b",  # MLA absorbed-decode up-projections (reshaped per head)
    "conv",          # SSM causal-conv taps (depthwise conv, not a matmul)
    "a_log",         # Mamba state matrix (elementwise exp)
    "r",             # sLSTM recurrent kernel (per-head einsum)
    "meta",          # Hymba meta tokens (concatenated, never multiplied)
    "g",             # norm gains (elementwise scale; layer-stacked gains
                     # reach min_ndim/min_size at full width)
)


def _dense_only(name: str) -> bool:
    parts = name.split("/")
    return any(p in parts for p in MATERIALIZE_DENSE_ONLY)


def deploy_params(
    params: Any,
    plan: DeploymentPlan,
    *,
    materialize: str = "dense",
    codec: str | None = None,
) -> Any:
    """Return a params pytree with deployed tensors replaced by achieved state.

    ``materialize`` chooses the serving representation of every deployed
    tensor (non-deployed leaves are always passed through dense):

    * ``"dense"`` (default / baseline) — the achieved f32 weights ``w_hat``;
      the model's matmuls stay ordinary dense dots.
    * ``"packed"`` — bit-packed crossbar operand dicts (the canonical packed
      planes the pool holds, ~8x less weight traffic); eligible matmuls run
      through ``simulator.cim_linear`` (see ``models.layers.linear``).
    * ``"planes_int8"`` — signed int8 plane operand dicts (one byte per bit
      cell); the parity/traffic baseline for the packed path.

    ``codec`` (default: the plan's ``config.codec``) applies the serving-side
    plane codec to packed operands (``planes.encode_operands``: plane-axis
    reorder + zero-tile flags).  Encoded operands are exact re-encodings —
    served tokens stay bit-identical to dense (pinned by
    ``tests/test_cim_packed.py``).

    Operand dicts are exact re-encodings of ``w_hat`` (same achieved weights,
    stucking included) — see ``simulator.operands_from_dense``.
    """
    if materialize not in MATERIALIZATIONS:
        raise ValueError(
            f"unknown materialize {materialize!r}; choose from {MATERIALIZATIONS}"
        )
    codec = plan.config.codec if codec is None else codec
    if codec not in planes.CODECS:
        raise ValueError(f"unknown plane codec {codec!r}; choose from {planes.CODECS}")
    if materialize != "dense":
        from repro.core import simulator

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if name not in plan.deployed:
            out.append(leaf)
            continue
        w_hat = jnp.asarray(plan.deployed[name])
        if materialize == "dense" or _dense_only(name):
            out.append(w_hat)
            continue
        r = plan.reports[name]
        out.append(
            simulator.operands_from_dense(
                w_hat, r.scale, r.offset, plan.spec.encoding, plan.spec.cols,
                materialize=materialize, codec=codec,
            )
        )
    return jax.tree_util.tree_unflatten(treedef, out)
