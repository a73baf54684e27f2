"""Online crossbar integrity: scrub, detect, localize, and self-repair.

``core/nonideal.py`` gave the pool one-directional faults — stuck cells are
injected and serving reads through them — but nothing ever *finds* which
stored bits went bad, let alone repairs them.  The fleet's only detector is
an end-to-end KL probe that can just kill a replica.  This module closes the
detect → localize → classify → repair loop, and prices every repair write in
the same transition/wear currency the planner optimizes (``price_pairs``),
turning the paper's endurance accounting into a live reliability policy:

* **Registration** (``IntegrityManager.register``, hooked into
  ``CrossbarPool.program``): each deployed tensor keeps its reference stored
  planes (``PoolProgramReport.achieved`` — the pool itself only retains the
  *last* section per chain), the expected read through the registration-time
  fault masks (``achieved_read`` — the deployment's bit-exact contract), and
  per-tile checksums over the expected read.  Tiles are
  ``IntegrityConfig.tile_bytes`` packed bytes (default 16 — one
  ``planes.OPERAND_TILE_BYTES`` tile = 128 weight rows), with a
  position-weighted byte sum per (section, tile, column): any single-byte
  change is detected (weights 1..16 make byte deltas non-cancelling) and an
  optional spare parity column (XOR of all data columns) cross-checks
  multi-column corruption.
* **Scrubbing** (``scrub_round``): a budgeted round-robin cursor over all
  registered tiles, meant to run *between* engine dispatch rounds
  (``Engine.attach_scrub``) so serving latency stays bounded.  A mismatching
  checksum triggers a re-read — a match on the second read classifies the
  event as **transient** drift (no repair) — then a deterministic masked
  read diffs against the expected planes to localize persistent faulty
  cells exactly.
* **Repair policy** (endurance-aware, per fault):
    1. **in-place rewrite** — stored bits drifted but cells still write
       (retention/state corruption): rewrite only the corrupted tile, cost =
       popcount of the toggle, charged to the owning crossbar's wear;
    2. **column remap** — cells that stay wrong after a verified rewrite are
       hard stuck-at; the faulty *stored column* is remapped onto a clean
       spare column plane (``col_map``), the column-granular cousin of the
       ``col_perm`` codec's reordering.  Low-order logical columns below
       ``tolerate_cols`` are instead tolerated un-repaired — exactly the
       paper's bit-stucking insight that LSB-plane errors are bounded;
    3. **section migration** — when spares are exhausted the whole section
       is rewritten into pristine spare pool capacity (cost = programming
       the full section), freeing its spares and clearing its masks.
  Every option is priced with ``hamming_ops.price_pairs`` and charged to the
  pool's wear/write counters; a per-round ``repair_budget`` caps repair
  writes (highest-significance columns repaired first, the remainder stays
  pending for the next round — ``pending_faults()`` is what the fleet's
  placement scoring reads to route around replicas mid-repair).
* **Refresh** (``rebuild``/``rebuild_plan``): repaired planes are
  dequantized through the planner's exact pipeline
  (``logical_from_physical`` → ``_dequant_slots`` → inverse permutation) so
  a repaired deployment is byte-identical to the original whenever every
  hard fault was remapped or migrated — the engine swaps it in atomically
  via ``hot_swap`` (in-flight streams keep their epoch's bit-exact
  contract).

Differential/fault-aware mapping (arXiv:2106.09166) and X-CHANGR
(arXiv:1907.00285) motivate the policy: targeted remapping recovers accuracy
at a small fraction of a full reprogram — ``benchmarks/integrity_scrub.py``
gates repair transitions at <= 0.5x the full-reprogram cost of the affected
tensors.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import planes as planes_mod
from repro.kernels.hamming import ops as hamming_ops

if TYPE_CHECKING:  # pool imports integrity lazily; keep the cycle type-only
    from repro.core.pool import CrossbarPool, PoolProgramReport


# ---------------------------------------------------------------------------
# Config + reports
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IntegrityConfig:
    """Scrub/repair policy knobs.

    ``spare_cols`` clean spare column planes are provisioned per section as
    remap targets (plus one reserved parity column when ``parity_col``);
    ``scrub_tiles`` bounds tiles verified per round so scrubbing between
    engine dispatches has bounded latency; ``repair_budget`` caps repair
    write transitions per round (None = unbounded; the first action of a
    round always proceeds so repair cannot live-lock); hard faults in
    logical columns below ``tolerate_cols`` are tolerated un-repaired (the
    bit-stucking insight: LSB-plane errors are bounded); ``transient_rate``
    models per-bit transient read flips that the re-read classifier must
    reject without spending repair writes.
    """

    tile_bytes: int = planes_mod.OPERAND_TILE_BYTES
    spare_cols: int = 2
    parity_col: bool = True
    scrub_tiles: int = 64
    repair_budget: int | None = None
    tolerate_cols: int = 0
    transient_rate: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.tile_bytes < 1:
            raise ValueError(f"tile_bytes must be >= 1, got {self.tile_bytes}")
        if self.spare_cols < 0:
            raise ValueError(f"spare_cols must be >= 0, got {self.spare_cols}")
        if self.scrub_tiles < 1:
            raise ValueError(f"scrub_tiles must be >= 1, got {self.scrub_tiles}")
        if self.repair_budget is not None and self.repair_budget < 1:
            raise ValueError(f"repair_budget must be >= 1 or None, got {self.repair_budget}")
        if self.tolerate_cols < 0:
            raise ValueError(f"tolerate_cols must be >= 0, got {self.tolerate_cols}")
        if not 0.0 <= self.transient_rate <= 1.0:
            raise ValueError(
                f"transient_rate must be in [0, 1], got {self.transient_rate}"
            )


@dataclasses.dataclass
class ScrubReport:
    """Counters from one (or an aggregation of) scrub round(s)."""

    rounds: int = 0
    tiles_scanned: int = 0
    detections: int = 0  # tiles with a persistent (non-transient) mismatch
    transients: int = 0  # tiles whose mismatch vanished on re-read
    localized_bits: int = 0  # faulty cells pinpointed by reference diff
    rewrites: int = 0  # in-place tile rewrites (retention corruption)
    remaps: int = 0  # column remaps onto spare planes (hard stuck-at)
    migrations: int = 0  # whole-section migrations to pristine capacity
    tolerated: int = 0  # hard-faulty low-order columns left un-repaired
    parity_mismatches: int = 0  # parity-column cross-check disagreements
    repair_transitions: int = 0  # total repair write cost (price_pairs)
    pending: int = 0  # repairs deferred past the round's write budget

    def merge(self, other: "ScrubReport") -> None:
        for f in dataclasses.fields(self):
            if f.name == "pending":
                self.pending = other.pending  # a level, not a flow
            else:
                setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class TensorRecord:
    """Integrity metadata + live modeled device state for one deployed tensor.

    ``reference`` is what the cells should *hold*, ``expected`` what a read
    should *return* (reference through the registration-time stuck masks —
    the deployment's contract).  ``stored``/``stuck0``/``stuck1`` are the
    live modeled cells that storms corrupt; ``col_map[s, c] >= cols`` means
    stored column ``c`` of section ``s`` has been remapped onto spare slot
    ``col_map[s, c] - cols``.
    """

    name: str
    reference: np.ndarray  # uint8[S, W, C] target stored bits (physical layout)
    expected: np.ndarray  # uint8[S, W, C] expected read (the serving contract)
    checksums: np.ndarray  # uint32[S, T, C] position-weighted tile sums
    parity: np.ndarray | None  # uint8[S, W] XOR of expected data columns
    sec_xbar: np.ndarray  # int32[S] owning physical crossbar per section
    col_order: np.ndarray | None  # int32[S, C] stored position -> logical plane
    transitions_full: int  # full-reprogram cost baseline (report.transitions_full)
    stored: np.ndarray  # uint8[S, W, C] live cell contents
    stuck0: np.ndarray  # uint8[S, W, C] live stuck-at-0 mask
    stuck1: np.ndarray  # uint8[S, W, C] live stuck-at-1 mask (disjoint)
    spare: np.ndarray  # uint8[S, W, n_spare] clean spare column planes
    spare_used: np.ndarray  # bool[S, n_spare]
    col_map: np.ndarray  # int32[S, C]
    detections: int = 0
    aux: dict[str, Any] | None = None  # planner-attached reconstruction closure


def tile_checksums(expected: np.ndarray, tile_bytes: int) -> np.ndarray:
    """Position-weighted byte sums per (section, tile, column) -> uint32[S, T, C].

    Weighting byte ``i`` within a tile by ``i + 1`` makes any single-byte
    delta non-cancelling (a plain XOR/sum misses even-multiplicity flips of
    the same bit position across bytes).
    """
    s, w, c = expected.shape
    t = -(-w // tile_bytes)
    pad = t * tile_bytes - w
    p = np.pad(expected, ((0, 0), (0, pad), (0, 0))).astype(np.uint32)
    p = p.reshape(s, t, tile_bytes, c)
    weights = np.arange(1, tile_bytes + 1, dtype=np.uint32)[None, None, :, None]
    return (p * weights).sum(axis=2, dtype=np.uint32)


def _price(a: np.ndarray, b: np.ndarray) -> int:
    """Total transitions a -> b on the shared Hamming path (Pallas on TPU,
    popcount elsewhere) — every repair write is priced here, never ad hoc."""
    a3 = a.reshape(-1, a.shape[-2], a.shape[-1]) if a.ndim == 3 else a[None]
    b3 = b.reshape(-1, b.shape[-2], b.shape[-1]) if b.ndim == 3 else b[None]
    if a3.shape[0] == 0:
        return 0
    return int(np.asarray(hamming_ops.price_pairs(jnp.asarray(a3), jnp.asarray(b3))).sum())


# ---------------------------------------------------------------------------
# The manager
# ---------------------------------------------------------------------------

class IntegrityManager:
    """Per-pool scrub/detect/repair state over all registered tensors."""

    def __init__(self, pool: "CrossbarPool", cfg: IntegrityConfig | None = None):
        self.pool = pool
        self.cfg = cfg or IntegrityConfig()
        self.rows = pool.spec.rows
        self.cols = pool.spec.cols
        self.words = -(-pool.spec.rows // 8)
        self.tensors: dict[str, TensorRecord] = {}
        self.totals = ScrubReport()
        self.spare_writes = 0  # repair writes landing on spare planes
        self._tiles: list[tuple[str, int, int]] = []
        self._segments: dict[str, tuple[int, int]] = {}
        self._cursor = 0
        self._clean_streak = 0
        self._pending: set[tuple[str, int, int]] = set()
        self._read_ctr = 0

    # -- registration ------------------------------------------------------

    def register(
        self,
        report: "PoolProgramReport",
        *,
        chains: list[np.ndarray],
        col_order: np.ndarray | None = None,
    ) -> TensorRecord:
        """Record a freshly programmed tensor's integrity metadata.

        Called by ``CrossbarPool.program`` when integrity is enabled; the
        expected read is ``achieved_read`` verbatim, so pre-existing pool
        faults at program time are part of the contract, not defects."""
        reference = np.asarray(report.achieved)
        expected = np.asarray(report.achieved_read)
        s = reference.shape[0]
        sec_xbar = np.zeros(s, np.int32)
        for j, c in enumerate(chains):
            sec_xbar[np.asarray(c)] = report.assignment[j]
        if self.pool.faults is not None:
            stuck0 = np.asarray(self.pool.faults.stuck0)[sec_xbar]
            stuck1 = np.asarray(self.pool.faults.stuck1)[sec_xbar]
        else:
            stuck0 = np.zeros_like(reference)
            stuck1 = np.zeros_like(reference)
        cfg = self.cfg
        rec = TensorRecord(
            name=report.name,
            reference=reference.copy(),
            expected=expected.copy(),
            checksums=tile_checksums(expected, cfg.tile_bytes),
            parity=self._parity_of(expected) if cfg.parity_col else None,
            sec_xbar=sec_xbar,
            col_order=None if col_order is None else np.asarray(col_order, np.int32),
            transitions_full=int(report.transitions_full),
            stored=reference.copy(),
            stuck0=stuck0.copy(),
            stuck1=stuck1.copy(),
            spare=np.zeros((s, self.words, cfg.spare_cols), np.uint8),
            spare_used=np.zeros((s, cfg.spare_cols), bool),
            col_map=np.tile(np.arange(self.cols, dtype=np.int32), (s, 1)),
        )
        self.tensors[report.name] = rec
        self._rebuild_tile_list()
        return rec

    def attach_aux(self, name: str, aux: dict[str, Any]) -> None:
        """Planner hook: the reconstruction closure (sign slots, quant scale/
        offset, inverse permutation, original shape) needed by ``rebuild``."""
        self.tensors[name].aux = aux

    def _parity_of(self, expected: np.ndarray) -> np.ndarray:
        out = np.zeros(expected.shape[:2], np.uint8)
        for c in range(expected.shape[2]):
            out ^= expected[:, :, c]
        return out

    def _rebuild_tile_list(self) -> None:
        tiles = []
        self._segments = {}  # name -> (S, T): shape of its tile grid
        for name, rec in self.tensors.items():
            t = rec.checksums.shape[1]
            self._segments[name] = (rec.reference.shape[0], t)
            tiles.extend((name, s, ti) for s in range(rec.reference.shape[0]) for ti in range(t))
        self._tiles = tiles
        self._cursor = 0
        self._clean_streak = 0

    @property
    def total_tiles(self) -> int:
        return len(self._tiles)

    # -- the modeled read path ---------------------------------------------

    def read(self, rec: TensorRecord, *, transient: bool = True) -> np.ndarray:
        """What the array returns for this tensor right now: live stored bits
        through the live stuck masks, remapped columns served from their
        spare planes, plus (optionally) transient per-read bit flips."""
        out = (rec.stored & ~rec.stuck0) | rec.stuck1
        remapped = np.argwhere(rec.col_map >= self.cols)
        if remapped.size:
            out = out.copy()
            for s, c in remapped:
                out[s, :, c] = rec.spare[s, :, rec.col_map[s, c] - self.cols]
        if transient and self.cfg.transient_rate > 0.0:
            self._read_ctr += 1
            rng = np.random.default_rng((self.cfg.seed, self._read_ctr))
            bits = rng.random((out.shape[0], self.rows, self.cols)) < self.cfg.transient_rate
            pad = self.words * 8 - self.rows
            if pad:
                bits = np.pad(bits, ((0, 0), (0, pad), (0, 0)))
            out = out ^ np.packbits(bits, axis=1)
        return out

    def verify_all(self) -> bool:
        """Deterministic full sweep: every tensor's read matches its contract."""
        return all(
            np.array_equal(self.read(rec, transient=False), rec.expected)
            for rec in self.tensors.values()
        )

    def pending_faults(self) -> int:
        """Known-but-unrepaired tiles (budget-deferred).  The fleet routes
        around replicas with pending faults and penalizes their score."""
        return len(self._pending)

    @property
    def clean(self) -> bool:
        """A full scrub cycle has passed with zero detections and no backlog."""
        return self._clean_streak >= len(self._tiles) and not self._pending

    # -- fault-storm injection ---------------------------------------------

    def storm(
        self,
        key: jax.Array,
        *,
        corrupt_rate: float = 0.0,
        stuck_rate: float = 0.0,
        tensors: list[str] | None = None,
    ) -> dict:
        """Deterministic mid-trace fault storm: flip stored bits at
        ``corrupt_rate`` (retention/state corruption — repairable in place)
        and add new stuck cells at ``stuck_rate`` (hard faults — need remap,
        migration, or tolerance).  Returns injected counts."""
        if not 0.0 <= corrupt_rate <= 1.0 or not 0.0 <= stuck_rate <= 1.0:
            raise ValueError("storm rates must be in [0, 1]")
        names = sorted(tensors if tensors is not None else self.tensors)
        corrupted = new_stuck = 0
        pad = self.words * 8 - self.rows
        for i, name in enumerate(names):
            rec = self.tensors[name]
            s = rec.stored.shape[0]
            k = jax.random.fold_in(key, i)
            kc, ks, kv = jax.random.split(k, 3)
            shape = (s, self.rows, self.cols)
            if corrupt_rate > 0.0:
                bits = np.asarray(jax.random.bernoulli(kc, corrupt_rate, shape))
                if pad:
                    bits = np.pad(bits, ((0, 0), (0, pad), (0, 0)))
                mask = np.packbits(bits, axis=1)
                rec.stored ^= mask
                corrupted += int(bits.sum())
            if stuck_rate > 0.0:
                cells = np.asarray(jax.random.bernoulli(ks, stuck_rate, shape))
                s1sel = np.asarray(jax.random.bernoulli(kv, 0.5, shape))
                if pad:
                    cells = np.pad(cells, ((0, 0), (0, pad), (0, 0)))
                    s1sel = np.pad(s1sel, ((0, 0), (0, pad), (0, 0)))
                cells_p = np.packbits(cells, axis=1)
                s1_p = np.packbits(cells & s1sel, axis=1)
                s0_new = (cells_p & ~s1_p) & ~rec.stuck1
                s1_new = s1_p & ~(rec.stuck0 | s0_new)
                rec.stuck0 |= s0_new
                rec.stuck1 |= s1_new
                new_stuck += _price(s0_new | s1_new, np.zeros_like(s0_new))
        return {
            "tensors": len(names),
            "corrupted_bits": corrupted,
            "new_stuck_cells": new_stuck,
        }

    # -- scrubbing ----------------------------------------------------------

    def scrub_round(self, budget_tiles: int | None = None) -> ScrubReport:
        """Verify up to ``budget_tiles`` tiles (default ``cfg.scrub_tiles``)
        from the round-robin cursor, classifying and repairing mismatches
        within the round's repair-write budget."""
        rep = ScrubReport(rounds=1)
        if not self._tiles:
            return rep
        n = min(budget_tiles or self.cfg.scrub_tiles, len(self._tiles))
        # per-tensor round read cache; checksum/parity comparisons run
        # vectorized over exactly the section range the round's window
        # covers, so the (overwhelmingly common) all-clean sweep is a
        # handful of whole-window numpy ops, not per-tile slicing
        cache: dict[str, np.ndarray] = {}
        tb = self.cfg.tile_bytes
        budget = self.cfg.repair_budget
        spent = 0

        scanned = 0
        while scanned < n:
            name, s, t = self._tiles[self._cursor]
            rec = self.tensors[name]
            if name not in cache:
                cache[name] = self.read(rec)
            read1 = cache[name]
            S, T = self._segments[name]
            flat = s * T + t
            limit = min(S * T - flat, n - scanned)
            sub = slice(s, (flat + limit - 1) // T + 1)  # sections in window
            bad = (tile_checksums(read1[sub], tb) != rec.checksums[sub]).any(axis=2)
            dirty = bad
            if rec.parity is not None:
                eq = np.bitwise_xor.reduce(read1[sub], axis=2) == rec.parity[sub]
                pad = (-eq.shape[1]) % tb
                if pad:
                    eq = np.pad(eq, ((0, 0), (0, pad)), constant_values=True)
                par_bad = ~eq.reshape(eq.shape[0], -1, tb).all(axis=2)
                dirty = bad | par_bad
            # bulk-advance the cursor over the window's run of clean tiles
            # (the steady-state path: one argmax, no per-tile work)
            off = flat - s * T  # window start within the sub-range
            hits = np.flatnonzero(dirty.reshape(-1)[off : off + limit])
            run = int(hits[0]) if hits.size else limit
            if run:
                if self._pending:
                    for p in [p for p in self._pending if p[0] == name]:
                        if flat <= p[1] * T + p[2] < flat + run:
                            self._pending.discard(p)
                rep.tiles_scanned += run
                self._clean_streak += run
                scanned += run
                self._cursor = (self._cursor + run) % len(self._tiles)
                continue
            # dirty tile at the cursor: per-tile classification + repair
            scanned += 1
            rep.tiles_scanned += 1
            sl = slice(t * tb, min((t + 1) * tb, rec.reference.shape[1]))
            if not bad.reshape(-1)[off]:  # checksum clean, parity caught it
                rep.parity_mismatches += 1
            # re-read: a transient flip vanishes on the second read
            read2 = self.read(rec)
            csums2 = tile_checksums(read2[s : s + 1, :, :], tb)[0]
            persistent = bool((csums2[t] != rec.checksums[s, t]).any())
            # deterministic localization: masked read diffed against the
            # expected (reference-through-masks) planes
            det = self.read(rec, transient=False)[s, sl, :] ^ rec.expected[s, sl, :]
            if not persistent or not det.any():
                rep.transients += 1
                self._clean_streak += 1
                self._cursor = (self._cursor + 1) % len(self._tiles)
                continue
            rep.detections += 1
            rec.detections += 1
            self._clean_streak = 0
            rep.localized_bits += _price(det, np.zeros_like(det))
            done, cost = self._repair_tile(
                rec, s, t, sl, rep, budget=budget, spent=spent
            )
            spent += cost
            cache.pop(name, None)  # repairs invalidate the round's cached read
            if not done:
                self._pending.add((name, s, t))
                rep.pending = len(self._pending)
                break  # budget exhausted: resume at this tile next round
            self._pending.discard((name, s, t))
            self._cursor = (self._cursor + 1) % len(self._tiles)
        rep.pending = len(self._pending)
        self.totals.merge(rep)
        return rep

    def scrub_until_clean(self, *, max_rounds: int = 10_000) -> ScrubReport:
        """Drive ``scrub_round`` until a full clean cycle (or ``max_rounds``).
        Aggregated report; ``clean`` tells whether convergence was reached."""
        agg = ScrubReport()
        for _ in range(max_rounds):
            agg.merge(self.scrub_round())
            if self.clean:
                break
        return agg

    # -- repair -------------------------------------------------------------

    def _afford(self, cost: int, budget: int | None, spent: int) -> bool:
        # the first action of a round always proceeds (progress guarantee)
        return budget is None or spent == 0 or spent + cost <= budget

    def _repair_tile(
        self,
        rec: TensorRecord,
        s: int,
        t: int,
        sl: slice,
        rep: ScrubReport,
        *,
        budget: int | None,
        spent: int,
    ) -> tuple[bool, int]:
        """Repair one persistently mismatching tile.  Returns (done, cost)."""
        cost = 0
        # 1) in-place rewrite of corrupted stored bits (cells still write)
        toggle = rec.stored[s, sl, :] ^ rec.reference[s, sl, :]
        if toggle.any():
            c_rw = _price(toggle, np.zeros_like(toggle))
            if not self._afford(c_rw, budget, spent + cost):
                return False, cost
            rec.stored[s, sl, :] = rec.reference[s, sl, :]
            self._charge_pool(int(rec.sec_xbar[s]), toggle, sl)
            rep.rewrites += 1
            rep.repair_transitions += c_rw
            cost += c_rw
        # 2) verified re-read: what survives a rewrite is hard stuck-at
        verify = self.read(rec, transient=False)
        resid = verify[s, sl, :] ^ rec.expected[s, sl, :]
        bad_cols = [c for c in range(self.cols) if resid[:, c].any()]
        # highest logical significance first: MSB-plane faults flip the
        # largest weight magnitudes, so they get the budget first
        def _logical(c: int) -> int:
            return int(rec.col_order[s, c]) if rec.col_order is not None else c

        for c in sorted(bad_cols, key=_logical, reverse=True):
            logical = _logical(c)
            if logical < self.cfg.tolerate_cols:
                # bit stucking: a low-order faulty column stays un-repaired;
                # the bounded LSB error becomes part of the serving contract
                rec.expected[s, :, c] = verify[s, :, c]
                rec.checksums[s, :, c] = tile_checksums(
                    rec.expected[s : s + 1], self.cfg.tile_bytes
                )[0, :, c]
                if rec.parity is not None:
                    rec.parity[s] = np.bitwise_xor.reduce(rec.expected[s], axis=1)
                rep.tolerated += 1
                continue
            free = np.flatnonzero(~rec.spare_used[s])
            if free.size:
                j = int(free[0])
                col = rec.expected[s, :, c]
                c_rm = _price(col[None, :, None], rec.spare[s, :, j][None, :, None])
                if not self._afford(c_rm, budget, spent + cost):
                    return False, cost
                rec.spare[s, :, j] = col
                rec.spare_used[s, j] = True
                rec.col_map[s, c] = self.cols + j
                self.spare_writes += c_rm
                self.pool.total_writes += c_rm
                rep.remaps += 1
                rep.repair_transitions += c_rm
                cost += c_rm
            else:
                c_mig = self._migrate_section(rec, s, budget=budget, spent=spent + cost)
                if c_mig is None:
                    return False, cost
                rep.migrations += 1
                rep.repair_transitions += c_mig
                cost += c_mig
                break  # the whole section is now pristine
        return True, cost

    def _migrate_section(
        self, rec: TensorRecord, s: int, *, budget: int | None, spent: int
    ) -> int | None:
        """Rewrite a whole section into pristine spare pool capacity (the
        least-worn crossbar).  Frees the section's spares, clears its live
        masks, and re-anchors the contract at the reference bits."""
        target = rec.expected[s]
        c_mig = _price(target, np.zeros_like(target))
        if not self._afford(c_mig, budget, spent):
            return None
        xbar = int(np.argmin(self.pool.wear_totals()))
        rec.sec_xbar[s] = xbar
        rec.stored[s] = rec.expected[s].copy()
        rec.reference[s] = rec.expected[s].copy()
        rec.stuck0[s] = 0
        rec.stuck1[s] = 0
        rec.col_map[s] = np.arange(self.cols, dtype=np.int32)
        rec.spare_used[s] = False
        rec.spare[s] = 0
        rec.checksums[s] = tile_checksums(rec.expected[s : s + 1], self.cfg.tile_bytes)[0]
        if rec.parity is not None:
            rec.parity[s] = np.bitwise_xor.reduce(rec.expected[s], axis=1)
        self._charge_pool(xbar, target, slice(0, rec.reference.shape[1]))
        return c_mig

    def _charge_pool(self, xbar: int, toggle: np.ndarray, sl: slice) -> None:
        """Charge a physical write's per-cell wear to the owning crossbar —
        repair writes spend the same endurance currency as programming."""
        bits = np.unpackbits(toggle, axis=0)
        row0 = sl.start * 8
        row1 = min(row0 + bits.shape[0], self.rows)
        if row1 > row0:
            self.pool.wear[xbar, row0:row1, :] += bits[: row1 - row0].astype(np.int64)
        self.pool.total_writes += int(bits.sum())

    # -- repaired-plane refresh --------------------------------------------

    def rebuild(self, name: str) -> jax.Array:
        """Dequantize the tensor's *current* read back into served weights —
        the planner's exact pipeline, so a fully repaired tensor reproduces
        the original deployment byte-for-byte."""
        from repro.core import planner as _planner  # lazy: avoid import cycle

        rec = self.tensors[name]
        if rec.aux is None:
            raise ValueError(
                f"tensor {name!r} has no reconstruction aux; deploy it through "
                "planner.build_deployment with integrity enabled"
            )
        arr = jnp.asarray(self.read(rec, transient=False))
        if rec.col_order is not None:
            arr = planes_mod.logical_from_physical(arr, jnp.asarray(rec.col_order))
        aux = rec.aux
        w_hat_slots = _planner._dequant_slots(
            arr, aux["sign_slots"], aux["scale"], aux["offset"], rows=self.rows
        )
        flat = w_hat_slots.reshape(-1)[aux["inv_perm"]][: aux["n"]]
        return flat.reshape(aux["shape"]).astype(aux["dtype"])

    def rebuild_plan(self, plan):
        """A ``DeploymentPlan`` whose deployed tensors reflect the current
        (possibly repaired) device state — feed to ``planner.deploy_params``
        and swap in atomically via ``Engine.hot_swap``."""
        deployed = dict(plan.deployed)
        for name in self.tensors:
            if name in deployed:
                deployed[name] = np.asarray(self.rebuild(name))
        return dataclasses.replace(plan, deployed=deployed)

    # -- reporting ----------------------------------------------------------

    def affected(self) -> list[str]:
        """Tensors with at least one persistent detection so far."""
        return sorted(n for n, r in self.tensors.items() if r.detections > 0)

    def transitions_full_affected(self) -> int:
        """Full-reprogram cost of every affected tensor — the baseline the
        repair-transition gate compares against."""
        return sum(self.tensors[n].transitions_full for n in self.affected())

    def summary(self) -> dict:
        return {
            "tensors": len(self.tensors),
            "tiles": self.total_tiles,
            "spare_cols": self.cfg.spare_cols,
            "parity_col": self.cfg.parity_col,
            "pending": self.pending_faults(),
            "clean": self.clean if self._tiles else True,
            "spare_writes": self.spare_writes,
            "totals": self.totals.to_dict(),
        }
