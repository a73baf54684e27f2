"""Online integrity subsystem (core/integrity.py): detection, localization,
classification, endurance-aware repair, and the engine scrub hook.

The contracts pinned here:

(1) registration parity — with integrity enabled the deployment's expected
    read is recorded at ``program()`` time and ``rebuild`` reproduces the
    deployed weights byte-for-byte;
(2) the scrub loop repairs every storm (corruption → in-place rewrite,
    hard stuck-at → spare-column remap or section migration) back to a
    bit-exact read, with every repair priced via ``price_pairs`` and
    charged to the pool's wear/write counters;
(3) transient read upsets are classified by re-read and never spend a
    repair write;
(4) the engine hook scrubs between dispatch rounds and atomically swaps
    repaired params in via ``hot_swap`` (epoch contract intact).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import integrity, nonideal
from repro.core.integrity import IntegrityConfig, tile_checksums
from repro.core.planner import (
    CrossbarSpec,
    PlannerConfig,
    _analyze_tensor_pool,
    build_deployment,
    deploy_params,
)
from repro.core.pool import CrossbarPool
from repro.launch.engine import Engine, EngineConfig, Request
from repro.launch.serve import generate
from repro.models import api

SPEC = CrossbarSpec(rows=64, cols=8)
PCFG = PlannerConfig(p_stuck=1.0, crossbars=4)


def _setup(icfg: IntegrityConfig | None = None, *, pcfg: PlannerConfig = PCFG,
           fault_model=None):
    """Fresh pool with integrity + one registered tensor; returns
    (pool, manager, deployed w_hat)."""
    pool = CrossbarPool(SPEC, 4, leveling="lpt")
    if fault_model is not None:
        pool.inject_faults(fault_model, jax.random.PRNGKey(5))
    mgr = pool.enable_integrity(icfg or IntegrityConfig())
    w = jax.random.normal(jax.random.PRNGKey(0), (40, 20)) * 0.05
    _, w_hat = _analyze_tensor_pool(w, SPEC, pcfg, jax.random.PRNGKey(1), pool, name="t0")
    return pool, mgr, w_hat


def test_integrity_config_validation():
    for bad in (
        dict(tile_bytes=0), dict(spare_cols=-1), dict(scrub_tiles=0),
        dict(repair_budget=0), dict(tolerate_cols=-1),
        dict(transient_rate=-0.1), dict(transient_rate=1.5),
    ):
        with pytest.raises(ValueError):
            IntegrityConfig(**bad)
    with pytest.raises(ValueError):
        _setup()[1].storm(jax.random.PRNGKey(0), corrupt_rate=2.0)


def test_register_clean_scrub_and_rebuild_parity():
    pool, mgr, w_hat = _setup()
    assert mgr.summary()["tensors"] == 1 and mgr.total_tiles > 0
    assert mgr.verify_all()
    rep = mgr.scrub_until_clean()
    assert rep.detections == 0 and rep.repair_transitions == 0 and mgr.clean
    np.testing.assert_array_equal(np.asarray(mgr.rebuild("t0")), np.asarray(w_hat))


def test_checksums_catch_single_byte_flip():
    planes = np.zeros((1, 16, 2), np.uint8)
    base = tile_checksums(planes, 16)
    for i in (0, 7, 15):
        mod = planes.copy()
        mod[0, i, 1] ^= 0x10
        assert (tile_checksums(mod, 16) != base).any(), f"byte {i} flip missed"


def test_corruption_localized_and_rewritten_in_place():
    """State corruption (writable cells) is localized exactly and repaired by
    in-place rewrites whose priced cost equals the corrupted bit count."""
    pool, mgr, w_hat = _setup()
    writes_before = pool.total_writes
    wear_before = pool.wear.sum()
    st = mgr.storm(jax.random.PRNGKey(7), corrupt_rate=5e-3)
    assert st["corrupted_bits"] > 0 and not mgr.verify_all()
    rep = mgr.scrub_until_clean()
    assert rep.detections > 0 and rep.rewrites > 0
    assert rep.remaps == 0 and rep.migrations == 0
    # exact localization + exact pricing: every corrupted bit found once,
    # every repair transition is one cell toggle charged to pool wear
    assert rep.localized_bits == st["corrupted_bits"]
    assert rep.repair_transitions == st["corrupted_bits"]
    assert pool.total_writes - writes_before == st["corrupted_bits"]
    assert pool.wear.sum() - wear_before == st["corrupted_bits"]
    assert mgr.verify_all() and mgr.clean
    np.testing.assert_array_equal(np.asarray(mgr.rebuild("t0")), np.asarray(w_hat))


def test_hard_stuck_remaps_to_spare_columns():
    pool, mgr, w_hat = _setup(IntegrityConfig(spare_cols=2))
    st = mgr.storm(jax.random.PRNGKey(9), stuck_rate=1e-3)
    assert st["new_stuck_cells"] > 0
    rep = mgr.scrub_until_clean()
    assert rep.remaps > 0
    rec = mgr.tensors["t0"]
    assert (rec.col_map >= SPEC.cols).sum() == rep.remaps
    assert mgr.verify_all() and mgr.clean
    np.testing.assert_array_equal(np.asarray(mgr.rebuild("t0")), np.asarray(w_hat))


def test_repair_far_cheaper_than_full_reprogram():
    pool, mgr, w_hat = _setup()
    mgr.storm(jax.random.PRNGKey(7), corrupt_rate=2e-3, stuck_rate=2e-4)
    rep = mgr.scrub_until_clean()
    full = mgr.transitions_full_affected()
    assert rep.detections > 0 and full > 0
    assert rep.repair_transitions <= 0.5 * full


def test_transient_flips_classified_not_repaired():
    pool, mgr, _ = _setup(IntegrityConfig(transient_rate=2e-3))
    before = mgr.tensors["t0"].stored.copy()
    rep = mgr.scrub_until_clean(max_rounds=50)
    assert rep.transients > 0
    assert rep.rewrites == 0 and rep.remaps == 0 and rep.repair_transitions == 0
    np.testing.assert_array_equal(mgr.tensors["t0"].stored, before)


def test_tolerate_cols_leaves_lsb_fault_unrepaired():
    """The bit-stucking insight: a hard fault in the lowest-order stored
    column is tolerated (no repair write) and folded into the contract."""
    pool, mgr, _ = _setup(IntegrityConfig(spare_cols=1, tolerate_cols=1))
    rec = mgr.tensors["t0"]
    rec.stuck1[0, 0, 0] |= 0x80  # stored column 0 == logical LSB (raw codec)
    rep = mgr.scrub_until_clean()
    assert rep.tolerated >= 1 and rep.remaps == 0 and rep.repair_transitions == 0
    assert mgr.verify_all() and mgr.clean  # contract re-anchored, reads stable


def test_spare_exhaustion_migrates_section():
    pool, mgr, w_hat = _setup(IntegrityConfig(spare_cols=1))
    rec = mgr.tensors["t0"]
    for c in (1, 2, 3):  # 3 hard-faulted columns, only 1 spare
        rec.stuck1[0, 0, c] |= 0x80
        for arr in (rec.expected, rec.reference, rec.stored):
            arr[0, 0, c] &= 0x7F  # ensure every fault conflicts
    rec.checksums[0] = tile_checksums(rec.expected[0:1], mgr.cfg.tile_bytes)[0]
    if rec.parity is not None:
        rec.parity[0] = np.bitwise_xor.reduce(rec.expected[0], axis=1)
    rep = mgr.scrub_until_clean()
    assert rep.migrations >= 1
    assert not rec.spare_used[0].any()  # migration frees the section's spares
    assert mgr.verify_all() and mgr.clean
    np.testing.assert_array_equal(np.asarray(mgr.rebuild("t0")), np.asarray(w_hat))


def test_repair_budget_defers_and_prioritizes_significance():
    """With a tiny per-round write budget only the highest-significance
    column is repaired first; the rest stays pending (fleet-visible) and
    converges over subsequent rounds."""
    pool, mgr, _ = _setup(IntegrityConfig(spare_cols=4, repair_budget=1))
    rec = mgr.tensors["t0"]
    # one low-order, one high-order hard fault in the same tile, in section
    # 0's two most-populated columns: a remap of an all-zero column writes
    # nothing and would not spend the budget
    pop = np.unpackbits(rec.expected[0], axis=0).sum(axis=0).astype(np.int64)
    lo, hi = sorted(np.argsort(-pop, kind="stable")[:2])
    assert pop[[lo, hi]].min() > 1
    for c in (lo, hi):
        rec.stuck1[0, 0, c] |= 0x80
        for arr in (rec.expected, rec.reference, rec.stored):
            arr[0, 0, c] &= 0x7F
    rec.checksums[0] = tile_checksums(rec.expected[0:1], mgr.cfg.tile_bytes)[0]
    if rec.parity is not None:
        rec.parity[0] = np.bitwise_xor.reduce(rec.expected[0], axis=1)
    rep1 = mgr.scrub_round()
    assert rep1.pending > 0 and mgr.pending_faults() > 0
    assert rec.col_map[0, hi] >= SPEC.cols  # MSB-side fault repaired first
    assert rec.col_map[0, lo] == lo  # LSB-side fault deferred past the budget
    mgr.scrub_until_clean()
    assert mgr.pending_faults() == 0 and mgr.verify_all() and mgr.clean


def test_registration_with_preexisting_faults_and_codec():
    """Pre-existing pool faults at program() time are the contract, not
    defects; under col_perm the stored layout round-trips through repair."""
    pool, mgr, w_hat = _setup(
        IntegrityConfig(spare_cols=2),
        pcfg=PlannerConfig(p_stuck=0.5, crossbars=4, codec="col_perm"),
        fault_model=nonideal.FaultModel(stuck0=0.01, stuck1=0.01),
    )
    assert mgr.tensors["t0"].col_order is not None
    assert mgr.verify_all()  # achieved_read IS the expectation
    assert mgr.scrub_until_clean().detections == 0
    mgr.storm(jax.random.PRNGKey(3), corrupt_rate=5e-3, stuck_rate=1e-3)
    mgr.scrub_until_clean()
    assert mgr.verify_all() and mgr.clean
    np.testing.assert_array_equal(np.asarray(mgr.rebuild("t0")), np.asarray(w_hat))


# ---------------------------------------------------------------------------
# engine integration: scrub between dispatches + atomic repaired refresh
# ---------------------------------------------------------------------------

LM_SPEC = CrossbarSpec(rows=128, cols=10)
LM_CFG = PlannerConfig(p_stuck=0.5, min_size=1024)
ECFG = EngineConfig(max_slots=2, page_size=8, max_seq_len=64, prefill_chunk=8,
                    decode_quantum=4)


@pytest.fixture(scope="module")
def gemma():
    cfg = get_arch("gemma-2b", reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, specs, rid0=0):
    out = []
    for i, (plen, gen) in enumerate(specs):
        rid = rid0 + i
        prompt = np.asarray(
            jax.random.randint(jax.random.PRNGKey(100 + rid), (plen,), 0, cfg.vocab_size)
        )
        out.append(Request(rid=rid, prompt=prompt, max_new_tokens=gen, greedy=True))
    return out


def test_engine_scrub_hook_repairs_and_refreshes(gemma):
    """Mid-trace storm: the engine's between-dispatch scrubber detects and
    repairs it, then hot-swaps the repaired planes in; requests served after
    the refresh are bit-identical to solo generation on the clean deployment."""
    cfg, params = gemma
    pool = CrossbarPool(LM_SPEC, LM_CFG.crossbars, leveling="lpt")
    # scrub_tiles covers the whole tile population: one engine dispatch round
    # is enough for the scrubber to find and repair the entire storm
    mgr = pool.enable_integrity(IntegrityConfig(spare_cols=2, scrub_tiles=1_000_000))
    plan = build_deployment(params, LM_SPEC, LM_CFG, pool=pool)
    clean = deploy_params(params, plan, materialize="dense")

    eng = Engine(cfg, clean, ECFG)
    eng.attach_scrub(
        mgr,
        refresh=lambda: deploy_params(params, mgr.rebuild_plan(plan), materialize="dense"),
    )
    # the storm corrupts the modeled cells; serving params degrade with the
    # swap below (what an un-refreshed engine would keep serving)
    mgr.storm(jax.random.PRNGKey(11), corrupt_rate=2e-3, stuck_rate=2e-4)
    corrupted = deploy_params(params, mgr.rebuild_plan(plan), materialize="dense")
    assert eng.hot_swap(corrupted)
    eng.run(_reqs(cfg, [(11, 5), (7, 6)]))
    assert eng.stats["scrub_rounds"] > 0
    assert eng.stats["scrub_detections"] > 0
    assert eng.stats["scrub_repairs"] > 0
    assert eng.stats["scrub_refreshes"] >= 1
    assert mgr.verify_all()
    # post-refresh admissions read the repaired (== original) planes
    post = _reqs(cfg, [(9, 6)], rid0=10)
    res = eng.run(post)[0]
    batch = {"tokens": jnp.asarray(post[0].prompt)[None]}
    toks, _ = generate(cfg, clean, batch, gen_len=post[0].max_new_tokens)
    assert res.tokens == [int(t) for t in np.asarray(toks[0])]
