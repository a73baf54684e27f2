"""Persistent crossbar pool: cross-tensor scheduling + per-cell wear accounting.

The paper's premise is finite memristor endurance, yet per-tensor pricing
(``planner._analyze_core``) restarts every tensor from a freshly pristine set
of L crossbars — an accounting fiction that cannot answer the deployment
question: how many writes does each *physical* cell absorb when a whole model
(or a sequence of models / checkpoints) streams through one fixed pool?
X-CHANGR-style remapping work shows cross-deployment reuse is where lifetime
is won or lost, so the pool is a first-class stateful subsystem here:

* ``CrossbarPool`` holds persistent packed crossbar state ``uint8[L, W, cols]``
  (the planner's canonical packed-plane representation) plus per-cell wear
  counters (host int64 — device int32 would wrap under long wear histories).
* ``program(sections, chains)`` carries state *across* calls: the first
  program of every chain is a **cross-tensor seam** priced from the pool's
  current content, not from pristine zeros.  All jobs are priced with the
  existing batched ``price_pairs`` path (Pallas ``hamming`` kernel on TPU,
  portable popcount elsewhere); an eager bool-plane twin (``impl="bool"``)
  reproduces every output bit-exactly and serves as the parity oracle.
* Wear-leveling chain→crossbar assignment (``leveling=``): ``"rotate"``
  seeds the chain walk at the least-worn crossbar; ``"lpt"`` runs the
  longest-processing-time greedy of ``schedule.lpt_assignment`` with
  capacity 1, seeded by accumulated per-crossbar wear, so heavy chains land
  on the least-worn crossbars; ``"fault"`` is the X-CHANGR-style remap of
  ``core.nonideal`` — chains are steered away from crossbars whose stuck
  cells would flip their high-order bits (falls back to ``"lpt"`` when no
  faults are injected).
* Non-ideal reads (``inject_faults``): a sampled ``nonideal.FaultState``
  attaches stuck-at masks per crossbar; ``PoolProgramReport.achieved_read``
  is what the array *reads back* through those masks — identical to
  ``achieved`` byte-for-byte at zero fault rate (the parity pin), and the
  planes the planner dequantizes into served weights.

Parity invariants (pinned by ``tests/test_pool.py``):

(a) with the pool ``reset()`` between tensors, streaming reproduces the
    planner's per-tensor ``transitions_*`` totals bit-exactly — the seam from
    an all-zero pool *is* the pristine initial program, and the stucked walk
    shares ``stucking._pad_chains``'s key schedule;
(b) wear conservation — the per-cell wear increments of a ``program`` call
    sum exactly to its programmed transitions (seams included);
(c) packed and bool implementations agree on every output.

Serving export: ``PoolProgramReport.achieved`` is the canonical packed
resident state per section after a program call — the planner dequantizes it
into the plan's ``deployed`` weights, and ``deploy_params(materialize=
"packed")`` re-encodes those into the bit-packed serving operands
(``simulator.operands_from_dense``; the re-encoding is bit-exact with the
pool's planes, pinned by ``tests/test_cim_packed.py``) — so ``serve --cim
--materialize packed`` computes on exactly the bits this pool holds.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitslice, cost, schedule
from repro.core.stucking import _pad_chains, _walk_packed
from repro.kernels.hamming import ops as hamming_ops

if TYPE_CHECKING:  # CrossbarSpec lives in planner; avoid the import cycle
    from repro.core.planner import CrossbarSpec


LEVELINGS = ("none", "rotate", "lpt", "fault")

DEFAULT_ENDURANCE = 1e8  # typical ReRAM cell write endurance (order of magnitude)


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PoolProgramReport:
    """Outcome of streaming one tensor's sections through the pool."""

    name: str
    assignment: np.ndarray  # int32[Lc] chain -> physical crossbar id
    seam_costs: np.ndarray  # int64[Lc] first program per chain, from pool state
    chain_totals: np.ndarray  # int64[Lc] full-reprogram totals (seam + intra)
    job_costs: np.ndarray  # int64[njobs] chain-major, seam job first per chain
    programmed_job_costs: np.ndarray  # int64[njobs] actually-programmed (stucked)
    transitions_full: int  # sum(job_costs): full reprogramming from pool state
    transitions_programmed: int  # == transitions_full when p_stuck >= 1
    wear_increment_total: int
    wear_increment_max: int
    achieved: jax.Array  # uint8[S, W, cols] resident state per section
    # what a read returns through the pool's fault masks (== achieved when
    # no faults are injected — zero-fault parity, tests/test_nonideal.py)
    achieved_read: jax.Array  # uint8[S, W, cols]


@dataclasses.dataclass(frozen=True)
class PoolStats:
    """Lifetime wear summary of a pool: how many physical writes its cells
    absorbed across every tensor programmed so far.  ``exhaustion_horizon``
    converts the worst cell into "how many such histories until the
    endurance budget dies" — the paper's motivating quantity made
    measurable (see docs/paper_map.md, endurance accounting)."""

    n_crossbars: int
    cells: int  # L * rows * cols physical memristors
    tensors_seen: int
    programs: int  # crossbar program operations (jobs) executed
    total_writes: int
    max_cell_writes: int
    mean_cell_writes: float

    def exhaustion_horizon(self, endurance: float = DEFAULT_ENDURANCE) -> float:
        """How many times the observed programming history could repeat before
        the most-worn cell exceeds ``endurance`` writes (inf if unworn)."""
        if self.max_cell_writes == 0:
            return float("inf")
        return endurance / self.max_cell_writes

    def to_dict(self, endurance: float = DEFAULT_ENDURANCE) -> dict:
        d = dataclasses.asdict(self)
        d["endurance"] = endurance
        d["exhaustion_horizon"] = self.exhaustion_horizon(endurance)
        return d


# ---------------------------------------------------------------------------
# Jitted packed helpers (retrace per shape bucket, like the planner core)
# ---------------------------------------------------------------------------

@jax.jit
def _price_intra_packed(packed: jax.Array, prev: jax.Array, cur: jax.Array) -> jax.Array:
    """Intra-chain job costs, batched: one ``price_pairs`` over all
    section-to-section steps of every chain (the gathers stay inside jit).
    Seams are priced separately — the chain→crossbar assignment, hence which
    pool state each seam reprograms, depends on these intra totals first."""
    return hamming_ops.price_pairs(packed[prev], packed[cur])


@partial(jax.jit, static_argnames=("rows",))
def _full_program_packed(
    state_assigned: jax.Array, packed: jax.Array,
    padded: jax.Array, valid: jax.Array, *, rows: int,
) -> tuple[jax.Array, jax.Array]:
    """p=1 pool walk, fully vectorized (no scan): every cell that differs is
    programmed, so per-cell wear is the XOR of consecutive resident states.

    Returns (wear int32[Lc, rows, cols], final states uint8[Lc, W, cols]).
    """
    seq = packed[padded]  # [Lc, T, W, cols]
    prev = jnp.concatenate([state_assigned[:, None], seq[:, :-1]], axis=1)
    tog = jnp.bitwise_xor(prev, seq)
    tog = jnp.where(valid[:, :, None, None], tog, jnp.uint8(0))
    bits = jnp.unpackbits(tog, axis=2, count=rows)  # [Lc, T, rows, cols]
    wear = jnp.sum(bits.astype(jnp.int32), axis=1)
    # padding repeats the last real section, so seq[:, -1] is the final state
    return wear, seq[:, -1]


@partial(jax.jit, static_argnames=("rows", "stuck_cols"))
@jax.named_scope("pool.stuck_walk")
def _stuck_program_packed(
    packed: jax.Array, padded: jax.Array, valid: jax.Array, keys: jax.Array,
    state_assigned: jax.Array, p: jax.Array | float, *, rows: int, stuck_cols: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """p<1 pool walk: ``stucking._walk_packed`` per chain, seeded with the
    pool's persistent states and accumulating per-cell wear.

    Returns (counts int32[Lc, T], wear int32[Lc, rows, cols],
    final states uint8[Lc, W, cols], achieved uint8[S, W, cols]).
    """
    _, states, counts, wear = jax.vmap(
        lambda o, v, k, s0: _walk_packed(
            packed, o, p, k, rows=rows, stuck_cols=stuck_cols,
            include_initial=True, valid=v, state0=s0, with_wear=True,
        )
    )(padded, valid, keys, state_assigned)
    # padded steps are masked no-ops (see stucking._pad_chains), so duplicate
    # indices in this scatter carry values identical to the last real visit
    achieved = packed.at[padded.reshape(-1)].set(
        states.reshape((-1,) + packed.shape[1:])
    )
    return counts, wear, states[:, -1], achieved


# ---------------------------------------------------------------------------
# Bool-plane oracle twin (eager, readable; bit-exact with the packed path)
# ---------------------------------------------------------------------------

def _program_bool_reference(
    planes: np.ndarray,  # bool[S, rows, cols] ideal section planes
    state_bool: np.ndarray,  # bool[Lc, rows, cols] assigned pool states
    chains: list[np.ndarray],
    p: float,
    key: jax.Array,
    *,
    stuck_cols: int,
) -> tuple[list[list[int]], np.ndarray, np.ndarray, np.ndarray]:
    """Eager per-chain walk mirroring the packed path's exact PRNG discipline:
    per-chain keys from one ``split(key, Lc)`` and per-step keys from
    ``split(chain_key, padded_len)`` — the schedule ``stucking._pad_chains``
    and ``_walk_packed`` use, so Bernoulli masks match draw for draw.

    Returns (per-chain per-step counts, wear int64[Lc, rows, cols],
    final states bool[Lc, rows, cols], achieved bool[S, rows, cols]).
    """
    max_len = max(len(c) for c in chains)
    chain_keys = jax.random.split(key, len(chains))
    achieved = np.array(planes, dtype=bool)
    wear = np.zeros(state_bool.shape, np.int64)
    finals = np.empty_like(state_bool)
    counts: list[list[int]] = []
    p32 = jnp.float32(p)  # match _walk_packed's float32 threshold exactly
    for i, ch in enumerate(chains):
        state = np.array(state_bool[i], dtype=bool)
        step_keys = jax.random.split(chain_keys[i], max_len)
        chain_counts = []
        for t, sec in enumerate(np.asarray(ch)):
            target = np.asarray(planes[sec])
            trans = state ^ target
            if p < 1.0 and stuck_cols > 0:
                mask = np.asarray(
                    jax.random.bernoulli(
                        step_keys[t], p32, (state.shape[0], stuck_cols)
                    )
                )
                program = trans.copy()
                program[:, :stuck_cols] &= mask
            else:
                program = trans
            state = np.where(program, target, state)
            wear[i] += program
            chain_counts.append(int(program.sum()))
            achieved[sec] = state
        finals[i] = state
        counts.append(chain_counts)
    return counts, wear, finals, achieved


# ---------------------------------------------------------------------------
# The pool
# ---------------------------------------------------------------------------

class CrossbarPool:
    """L physical crossbars with persistent content and per-cell wear.

    ``state`` is packed exactly like the planner's canonical planes
    (``uint8[L, ceil(rows/8), cols]``, rows packed MSB-first); ``wear`` is a
    host ``int64[L, rows, cols]`` counter of programmed transitions per cell.
    """

    def __init__(self, spec: "CrossbarSpec", n_crossbars: int, *, leveling: str = "none"):
        if leveling not in LEVELINGS:
            raise ValueError(f"unknown pool leveling {leveling!r}; choose from {LEVELINGS}")
        if n_crossbars < 1:
            raise ValueError("pool needs at least one crossbar")
        if spec.rows < 1 or spec.cols < 1:
            raise ValueError(
                f"crossbar geometry must be positive, got {spec.rows}x{spec.cols}"
            )
        self.spec = spec
        self.n_crossbars = int(n_crossbars)
        self.leveling = leveling
        self._words = -(-spec.rows // 8)
        self._state = jnp.zeros((self.n_crossbars, self._words, spec.cols), jnp.uint8)
        self.wear = np.zeros((self.n_crossbars, spec.rows, spec.cols), np.int64)
        self.tensors_seen = 0
        self.programs = 0
        self.total_writes = 0
        self.faults = None  # Optional[nonideal.FaultState]
        self.integrity = None  # Optional[integrity.IntegrityManager]

    # -- integrity ---------------------------------------------------------

    def enable_integrity(self, cfg=None):
        """Attach an :class:`~repro.core.integrity.IntegrityManager`.

        Once enabled, every ``program()`` call registers the tensor's
        reference planes, per-tile checksums, and spare columns with the
        manager, so the scrub/detect/repair loop (``core/integrity.py``) can
        verify and repair the deployment online.  Returns the manager (also
        kept on ``self.integrity``).
        """
        from repro.core import integrity  # local: pool <-> integrity cycle hygiene

        self.integrity = integrity.IntegrityManager(
            self, cfg or integrity.IntegrityConfig()
        )
        return self.integrity

    # -- faults ------------------------------------------------------------

    def inject_faults(self, model, key: jax.Array | None = None):
        """Sample and attach a ``nonideal.FaultState`` for this pool.

        Deterministic per (model, key).  Once attached, every
        ``program()`` report's ``achieved_read`` passes through the stuck
        masks and the ``"fault"`` leveling has damage information to remap
        against.  Returns the state (also kept on ``self.faults``).
        """
        from repro.core import nonideal  # local: planner <-> pool cycle hygiene

        if key is None:
            key = jax.random.PRNGKey(0)
        self.faults = nonideal.inject(self.spec, self.n_crossbars, model, key)
        return self.faults

    def read_state(self) -> np.ndarray:
        """Host copy of the pool content *as read* through any fault masks."""
        if self.faults is None:
            return self.state
        from repro.core import nonideal

        return np.asarray(
            nonideal.read_packed(self._state, self.faults.stuck0, self.faults.stuck1)
        )

    # -- introspection -----------------------------------------------------

    @property
    def state(self) -> np.ndarray:
        """Host copy of the packed pool content uint8[L, W, cols]."""
        return np.asarray(self._state)

    def wear_totals(self) -> np.ndarray:
        """Accumulated writes per crossbar -> int64[L]."""
        return self.wear.sum(axis=(1, 2))

    def stats(self) -> PoolStats:
        return PoolStats(
            n_crossbars=self.n_crossbars,
            cells=int(self.wear.size),
            tensors_seen=self.tensors_seen,
            programs=self.programs,
            total_writes=self.total_writes,
            max_cell_writes=int(self.wear.max()),
            mean_cell_writes=float(self.wear.mean()),
        )

    def reset(self, *, wear: bool = False) -> None:
        """Zero the crossbar content (and optionally the wear history).

        Resetting content between tensors recovers the planner's per-tensor
        pristine accounting bit-exactly (parity invariant (a)); wear normally
        survives resets — erasing a crossbar is itself free only in this
        simplified model, but the counters exist to *accumulate* lifetimes.
        """
        self._state = jnp.zeros_like(self._state)
        if wear:
            self.wear[:] = 0
            self.tensors_seen = 0
            self.programs = 0
            self.total_writes = 0

    # -- chain -> crossbar assignment --------------------------------------

    def _assign(
        self,
        chain_costs: np.ndarray,
        leveling: str,
        *,
        packed: jax.Array | None = None,
        chains: list[np.ndarray] | None = None,
    ) -> np.ndarray:
        lc = chain_costs.shape[0]
        if leveling == "none":
            return np.arange(lc, dtype=np.int32)
        if leveling == "rotate":
            # seed the contiguous chain block at the least-worn crossbar
            start = int(np.argmin(self.wear_totals()))
            return ((start + np.arange(lc)) % self.n_crossbars).astype(np.int32)
        if leveling == "fault" and self.faults is not None and packed is not None:
            # X-CHANGR-style remap: steer damage-sensitive chains away from
            # crossbars whose stuck cells would flip their high-order bits,
            # ties broken toward least wear (core/nonideal.py)
            from repro.core import nonideal

            damage = nonideal.damage_matrix(packed, chains, self.faults)
            return nonideal.fault_aware_assignment(damage, wear=self.wear_totals())
        # "lpt" (and "fault" with no injected faults — nothing to avoid,
        # wear-level instead): heaviest chains to least-worn crossbars, one
        # chain per crossbar (capacity 1 — chains program in parallel on
        # distinct hardware), loads seeded with accumulated wear
        tids, _ = schedule.lpt_assignment(
            chain_costs, self.n_crossbars,
            initial_loads=self.wear_totals(), capacity=1,
        )
        return tids

    # -- programming -------------------------------------------------------

    def program(
        self,
        packed: jax.Array,
        chains: list[np.ndarray],
        *,
        p_stuck: float = 1.0,
        key: jax.Array | None = None,
        stuck_cols: int = 1,
        leveling: str | None = None,
        impl: str = "packed",
        name: str = "w",
    ) -> PoolProgramReport:
        """Stream one tensor's sections through the pool along ``chains``.

        ``packed`` are canonical packed planes ``uint8[S, W, cols]`` (bool
        planes are packed on entry), or a :class:`~repro.core.planes.PlaneSet`
        — a codec-encoded stored representation, in which case the pool
        programs its ``physical()`` bits: the words the crossbar actually
        holds (permuted columns for ``col_perm``, reconstructed constants for
        ``const_rle``).  Seam pricing, wear counters, and fault masks all see
        those physical bits, so endurance accounting stays exact under every
        codec; the caller recovers logical planes from ``achieved_read`` with
        ``planes.logical_from_physical`` *after* the (possibly faulty) read.
        Each chain is assigned a physical crossbar (``leveling=None`` defers
        to the pool's own setting); its first program reprograms whatever
        that crossbar currently holds — the cross-tensor seam.  State and
        wear counters are updated in place; per-job costs, seams, and wear
        increments come back in the report.  Every program is counted
        (``include_initial`` semantics are inherently True for a pool: the
        seam is a physical write).
        """
        with jax.profiler.TraceAnnotation("pool.program") as span:
            if impl not in ("packed", "bool"):
                raise ValueError(f"unknown pool impl: {impl!r}")
            leveling = self.leveling if leveling is None else leveling
            if leveling not in LEVELINGS:
                raise ValueError(f"unknown pool leveling {leveling!r}; choose from {LEVELINGS}")
            col_order = None
            if hasattr(packed, "physical"):  # PlaneSet: program the stored bits
                if getattr(packed, "col_order", None) is not None:
                    col_order = np.asarray(packed.col_order)
                packed = packed.physical()
            packed = jnp.asarray(packed)
            if packed.dtype != jnp.uint8:
                packed = bitslice.pack_rows(packed)
            s, words, cols = packed.shape
            if (words, cols) != (self._words, self.spec.cols):
                raise ValueError(
                    f"section planes {packed.shape} do not fit pool geometry "
                    f"{self.spec.rows}x{self.spec.cols}"
                )
            chains = [np.asarray(c, dtype=np.int32) for c in chains]
            lc = len(chains)
            if not 1 <= lc <= self.n_crossbars:
                raise ValueError(f"{lc} chains for a pool of {self.n_crossbars} crossbars")
            if key is None:
                key = jax.random.PRNGKey(0)
            rows = self.spec.rows
            full = p_stuck >= 1.0 or stuck_cols == 0

            span.set_metadata(chains=lc)

            planes = bitslice.unpack_rows(packed, rows) if impl == "bool" else None

            # --- intra-chain job costs (assignment-independent) ----------------
            prev_i, cur_i = schedule.chain_pairs(chains, include_initial=False)
            with jax.profiler.TraceAnnotation("pool.price_intra"):
                if not prev_i.size:
                    intra = np.zeros((0,), np.int64)
                elif impl == "packed":
                    intra = _price_intra_packed(packed, prev_i, cur_i)
                else:
                    intra = cost.pair_transitions(planes[prev_i], planes[cur_i])
            with jax.profiler.TraceAnnotation("pool.price_intra.readback"):
                intra = np.asarray(intra, np.int64)
            lens = [len(c) - 1 for c in chains]
            intra_per_chain = np.split(intra, np.cumsum(lens)[:-1]) if lc else []
            chain_intra = np.array([x.sum() for x in intra_per_chain], np.int64)

            # --- chain -> crossbar assignment + seam pricing --------------------
            with jax.profiler.TraceAnnotation("pool.assign"):
                assignment = self._assign(chain_intra, leveling, packed=packed, chains=chains)
                firsts = np.array([c[0] for c in chains], np.int32)
                assignment_dev = jnp.asarray(assignment)
                state_assigned = self._state[assignment_dev]
            with jax.profiler.TraceAnnotation("pool.seam"):
                if impl == "packed":
                    seam = hamming_ops.price_pairs(state_assigned, packed[firsts])
                else:
                    state_bool = np.asarray(bitslice.unpack_rows(self._state, rows))[assignment]
                    seam = cost.pair_transitions(jnp.asarray(state_bool), planes[firsts])
            with jax.profiler.TraceAnnotation("pool.seam.readback"):
                seam = np.asarray(seam, np.int64)
            job_costs = np.concatenate(
                [np.concatenate([seam[j : j + 1], intra_per_chain[j]]) for j in range(lc)]
            )
            chain_totals = seam + chain_intra

            # --- the physical walk: wear, final states, achieved planes ---------
            with jax.profiler.TraceAnnotation("pool.walk"):
                padded, valid, keys = _pad_chains(chains, key)
                counts, programmed_job_costs = None, job_costs
                if impl == "bool":
                    counts_b, wear_inc, finals_b, achieved_b = _program_bool_reference(
                        np.asarray(planes), state_bool, chains, p_stuck, key,
                        stuck_cols=stuck_cols,
                    )
                    programmed_job_costs = np.array(
                        [c for per_chain in counts_b for c in per_chain], np.int64
                    )
                    new_states = bitslice.pack_rows(jnp.asarray(finals_b))
                    achieved = bitslice.pack_rows(jnp.asarray(achieved_b))
                elif full:
                    wear_inc, new_states = _full_program_packed(
                        state_assigned, packed, padded, valid, rows=rows
                    )
                    achieved = packed
                else:
                    counts, wear_inc, new_states, achieved = _stuck_program_packed(
                        packed, padded, valid, keys, state_assigned, p_stuck,
                        rows=rows, stuck_cols=stuck_cols,
                    )
            with jax.profiler.TraceAnnotation("pool.walk.readback"):
                if counts is not None:
                    counts = np.asarray(counts, np.int64)
                    programmed_job_costs = np.concatenate(
                        [counts[j, : len(c)] for j, c in enumerate(chains)]
                    )
                wear_inc = np.asarray(wear_inc, np.int64)

            # --- non-ideal readback ---------------------------------------------
            if self.faults is None:
                achieved_read = achieved
            else:
                from repro.core import nonideal

                sec_xbar = np.zeros(s, np.int32)
                for j, c in enumerate(chains):
                    sec_xbar[c] = assignment[j]
                idx = jnp.asarray(sec_xbar)
                achieved_read = nonideal.read_packed(
                    achieved, self.faults.stuck0[idx], self.faults.stuck1[idx]
                )

            # --- commit ---------------------------------------------------------
            with jax.profiler.TraceAnnotation("pool.commit"):
                self._state = self._state.at[assignment_dev].set(new_states)
                self.wear[assignment] += wear_inc
                self.tensors_seen += 1
                self.programs += int(job_costs.shape[0])
                wear_total = int(wear_inc.sum())
                self.total_writes += wear_total

                report = PoolProgramReport(
                    name=name,
                    assignment=assignment,
                    seam_costs=seam,
                    chain_totals=chain_totals,
                    job_costs=job_costs,
                    programmed_job_costs=programmed_job_costs,
                    transitions_full=int(job_costs.sum()),
                    transitions_programmed=int(programmed_job_costs.sum()),
                    wear_increment_total=wear_total,
                    wear_increment_max=int(wear_inc.max()),
                    achieved=achieved,
                    achieved_read=achieved_read,
                )
                if self.integrity is not None:
                    # register reference planes + tile checksums for the scrub loop
                    self.integrity.register(report, chains=chains, col_order=col_order)
            return report
