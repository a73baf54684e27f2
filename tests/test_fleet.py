"""Fault-tolerant serving fleet: routing, failover, hedging, admission.

The fleet-level acceptance contract extends the engine's: every request a
:class:`Fleet` *completes* — through crashes, stalls, hedged duplicate
dispatches, operator kills/drains/restores, and corrupted health probes —
emits a token stream bit-identical to running it alone through
``launch.serve.generate`` with the same PRNG seed.  Chaos routes requests
around; it never changes their tokens.  Requests the fleet does NOT
complete fail loudly and cheaply: deadline expiry retires as ``"timeout"``
with partial tokens, admission overflow as ``"shed"``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.engine import EngineConfig, HealthConfig, HealthMonitor, Request
from repro.launch.fleet import (
    ChaosEvent,
    FaultInjector,
    Fleet,
    FleetConfig,
    FleetResult,
)
from repro.launch.mesh import replica_devices
from repro.launch.serve import generate
from repro.models import api
from repro.runtime.fault import FaultPolicy

ECFG = EngineConfig(
    max_slots=2, page_size=8, max_seq_len=64, prefill_chunk=16, decode_quantum=4
)


@pytest.fixture(scope="module")
def gemma():
    cfg = get_arch("gemma-2b", reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk(cfg, rid, plen, gen, seed=0, greedy=False, **kw):
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(100 + rid), (plen,), 0, cfg.vocab_size)
    )
    return Request(rid=rid, prompt=prompt, max_new_tokens=gen, greedy=greedy,
                   seed=seed, **kw)


def _solo(cfg, params, req):
    batch = {"tokens": jnp.asarray(req.prompt)[None]}
    toks, _ = generate(cfg, params, batch, gen_len=req.max_new_tokens,
                       greedy=req.greedy, seed=req.seed)
    return [int(t) for t in np.asarray(toks[0])]


def _assert_parity(cfg, params, fleet, reqs, results):
    for req, res in zip(reqs, results):
        assert res.status == "ok", (req.rid, res)
        # degraded mode may have clamped max_new_tokens: compare against the
        # request as the fleet actually admitted it
        eff = fleet.requests[req.rid]
        assert res.tokens == _solo(cfg, params, eff), f"rid {req.rid}"


# ---------------------------------------------------------------------------
# Config + injector basics
# ---------------------------------------------------------------------------

def test_fleet_config_validation():
    for bad in (
        dict(n_replicas=0),
        dict(max_queue=0),
        dict(failover="panic"),
        dict(hedge_stall_s=0.0),
        dict(hedge_after_marks=0),
    ):
        with pytest.raises(ValueError):
            FleetConfig(**bad)
    assert FleetConfig(max_queue=10).degrade_at == 5
    assert FleetConfig(max_queue=10, degrade_backlog=8).degrade_at == 8


def test_fault_injector_fires_once_per_event_and_logs():
    inj = FaultInjector()
    inj.crash(0, at_step=2, lose_state=True)
    inj.stall(1, at_step=0, duration_s=1.0)
    assert inj.fire(0, 0, now=0.0) == []  # not yet reached
    assert inj.fire(1, 0, now=0.0)[0].kind == "stall"
    fired = inj.fire(0, 5, now=1.0)  # past at_step still fires (once)
    assert fired[0].kind == "crash" and fired[0].lose_state
    assert inj.fire(0, 6, now=2.0) == []  # never re-fires
    assert [e["kind"] for e in inj.log] == ["stall", "crash"]


def test_replica_devices_wraps_over_available():
    devs = replica_devices(3)
    assert len(devs) == 3 and all(d in jax.devices() for d in devs)
    with pytest.raises(ValueError):
        replica_devices(0)


# ---------------------------------------------------------------------------
# Routing parity (no chaos)
# ---------------------------------------------------------------------------

def test_fleet_parity_no_chaos(gemma):
    """Requests spread over 2 replicas all complete bit-identical to solo;
    placement balances rather than piling onto one replica."""
    cfg, params = gemma
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=2, hedge=False), ECFG)
    reqs = [_mk(cfg, i, 4 + i, 6, seed=i, greedy=(i % 2 == 0)) for i in range(4)]
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["completed"] == 4 and fleet.stats["shed"] == 0
    assert {r.replica for r in results} == {0, 1}  # both replicas served


# ---------------------------------------------------------------------------
# Crash failover
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lose_state", [False, True])
def test_crash_failover_parity(gemma, lose_state):
    """Killing a replica mid-decode re-routes its requests: with host state
    intact they resume teacher-forced from the recorded prefix
    (``failovers``), with state lost they restart from scratch
    (``restarts``) — the stream is identical either way."""
    cfg, params = gemma
    inj = FaultInjector()
    inj.crash(0, at_step=1, lose_state=lose_state)
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=2, hedge=False), ECFG,
                  injector=inj)
    reqs = [_mk(cfg, i, 5 + i, 8, seed=i) for i in range(4)]
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["crashes"] == 1 and inj.log[0]["kind"] == "crash"
    assert fleet.replicas[0].state == "dead"
    moved = fleet.stats["failovers"] + fleet.stats["restarts"]
    assert moved >= 1 and fleet.stats["retries"] == moved
    if lose_state:
        assert fleet.stats["failovers"] == 0  # nothing salvageable
    # exactly the re-routed requests record the extra placement attempt
    assert sum(r.attempts >= 2 for r in results) == moved


def test_dispatch_exception_is_a_crash(gemma):
    """A real exception out of ``Engine.step`` (not injected) fails the
    replica over instead of killing the fleet loop."""
    cfg, params = gemma
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=2, hedge=False), ECFG)
    boom = {"armed": True}
    orig = fleet.replicas[0].engine.step

    def bad_step(now):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("device dispatch failed")
        return orig(now)

    fleet.replicas[0].engine.step = bad_step
    reqs = [_mk(cfg, i, 5, 6, seed=40 + i) for i in range(3)]
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["crashes"] == 1
    assert fleet.replicas[0].state == "dead"


def test_all_replicas_dead_raises(gemma):
    cfg, params = gemma
    inj = FaultInjector()
    inj.crash(0, at_step=0)
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=1, hedge=False), ECFG,
                  injector=inj)
    with pytest.raises(RuntimeError, match="every replica"):
        fleet.run([_mk(cfg, 0, 5, 6)])


# ---------------------------------------------------------------------------
# Stalls + hedged dispatch
# ---------------------------------------------------------------------------

def test_stall_triggers_hedge_first_finisher_wins(gemma):
    """A stalled replica's in-flight requests are duplicated onto a healthy
    one; the duplicate finishes first, the stalled copy is cancelled, and
    the adopted stream is still exact."""
    cfg, params = gemma
    inj = FaultInjector()
    inj.stall(1, at_step=1, duration_s=30.0)
    fleet = Fleet(
        cfg, params,
        FleetConfig(n_replicas=2, hedge=True, hedge_stall_s=0.1), ECFG,
        injector=inj,
    )
    reqs = [_mk(cfg, i, 4 + i, 6, seed=10 + i) for i in range(4)]
    t0 = time.perf_counter()
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["stalls"] == 1 and fleet.stats["hedges"] >= 1
    assert fleet.stats["cancels"] >= 1  # the losing copies were cancelled
    assert any(r.hedged for r in results)
    # first finisher wins: the adopted copies ran on the healthy replica,
    # and the trace never waited out the 30s stall
    assert all(r.replica == 0 for r in results if r.hedged)
    assert time.perf_counter() - t0 < 25.0


def test_slow_replica_accumulates_straggler_marks(gemma):
    """slow-by-factor chaos inflates the replica's reported step wall; the
    per-replica EWMA marks it and the mark count feeds placement cost."""
    cfg, params = gemma
    inj = FaultInjector()
    inj.slow(0, at_step=3, factor=1e5, steps=8)
    fleet = Fleet(
        cfg, params,
        FleetConfig(n_replicas=2, hedge=True, hedge_after_marks=2,
                    hedge_stall_s=30.0), ECFG,
        injector=inj,
    )
    reqs = [_mk(cfg, i, 5, 16, seed=20 + i) for i in range(4)]
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["slows"] == 1
    assert len(fleet.replicas[0].straggler.events) >= 1


# ---------------------------------------------------------------------------
# Deadlines, shedding, degraded mode
# ---------------------------------------------------------------------------

def test_deadline_timeout_returns_partial_prefix(gemma):
    """A request that cannot finish inside its deadline retires as
    ``"timeout"`` with whatever tokens it emitted — a strict prefix of the
    solo stream — while its neighbours complete normally."""
    cfg, params = gemma
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=1, hedge=False), ECFG)
    slow = _mk(cfg, 0, 5, 40, seed=0, deadline_s=0.05)
    fine = _mk(cfg, 1, 5, 6, seed=1)
    res = fleet.run([slow, fine])
    assert res[0].status == "timeout"
    assert res[0].tokens == _solo(cfg, params, slow)[: len(res[0].tokens)]
    assert res[1].status == "ok" and res[1].tokens == _solo(cfg, params, fine)
    assert fleet.stats["timeouts"] == 1


def test_default_deadline_applies_to_undated_requests(gemma):
    cfg, params = gemma
    fleet = Fleet(
        cfg, params,
        FleetConfig(n_replicas=1, hedge=False, default_deadline_s=0.05), ECFG,
    )
    res = fleet.run([_mk(cfg, 0, 5, 64 - 5, seed=0)])
    assert res[0].status == "timeout"
    assert fleet.requests[0].deadline_s == 0.05


def test_bounded_queue_sheds_and_degrades(gemma):
    """Backlog beyond ``max_queue`` is shed (recorded, never queued);
    between ``degrade_backlog`` and the cap new requests get their
    ``max_new_tokens`` clamped — and the clamped streams are still exact."""
    cfg, params = gemma
    fleet = Fleet(
        cfg, params,
        FleetConfig(n_replicas=1, max_queue=3, degrade_backlog=2,
                    degrade_cap=2, hedge=False), ECFG,
    )
    reqs = [_mk(cfg, i, 4, 8, seed=30 + i) for i in range(6)]
    results = fleet.run(reqs)
    shed = [r for r in results if r.status == "shed"]
    ok = [r for r in results if r.status == "ok"]
    assert len(shed) == fleet.stats["shed"] >= 1
    assert fleet.stats["degraded"] >= 1
    assert all(r.tokens == [] and r.replica is None for r in shed)
    for r in ok:
        eff = fleet.requests[r.rid]
        assert r.tokens == _solo(cfg, params, eff), f"rid {r.rid}"
    clamped = [r for r in ok if fleet.requests[r.rid].max_new_tokens == 2]
    assert clamped, "degraded mode never clamped anything"


# ---------------------------------------------------------------------------
# Lifecycle: kill / drain / restore, health probes
# ---------------------------------------------------------------------------

def test_kill_drain_restore_lifecycle(gemma):
    """Operator lifecycle mid-trace: kill fails work over, drain migrates
    the waiting line and parks when empty, restore brings a dead replica
    back — all streams stay exact throughout."""
    cfg, params = gemma
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=3, hedge=False), ECFG)
    reqs = [_mk(cfg, i, 4 + i, 10, seed=20 + i) for i in range(6)]
    for r in reqs:
        fleet.submit(r)
    t0, cycle = time.perf_counter(), 0
    while not all(q.rid in fleet.results for q in reqs):
        now = time.perf_counter() - t0
        cycle += 1
        if cycle == 2:
            fleet.kill(1, now)
        if cycle == 3:
            fleet.drain(2, now)
        if cycle == 5:
            fleet.restore(1, now)
        fleet.step(now)
        assert cycle < 10_000
    results = [fleet.results[q.rid] for q in reqs]
    _assert_parity(cfg, params, fleet, reqs, results)
    s = fleet.stats
    assert s["kills"] == 1 and s["drains"] == 1 and s["restores"] == 1
    assert fleet.replicas[1].state == "live"
    assert fleet.replicas[2].state in ("draining", "down")


def test_restore_undrains_without_losing_work(gemma):
    cfg, params = gemma
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=1, hedge=False), ECFG)
    req = _mk(cfg, 0, 5, 6, seed=3)
    fleet.submit(req)
    fleet.step(0.0)
    fleet.replicas[0].state = "draining"
    fleet.restore(0)  # un-drain: same engine, in-flight slot intact
    assert fleet.replicas[0].state == "live"
    t0 = time.perf_counter()
    while 0 not in fleet.results:
        fleet.step(time.perf_counter() - t0)
    assert fleet.results[0].tokens == _solo(cfg, params, req)


def test_corrupt_probe_kills_healthy_replica_fleet_recovers(gemma):
    """corrupt-health-probe chaos: the probe lies, the fleet kills a
    perfectly healthy replica — and the failover path still completes every
    stream exactly."""
    cfg, params = gemma
    batch = {"tokens": jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(9), (1, 8), 0, cfg.vocab_size))}
    monitor = HealthMonitor(cfg, params, batch)
    inj = FaultInjector()
    inj.corrupt_probe(0, at_step=1)
    fleet = Fleet(
        cfg, params,
        FleetConfig(n_replicas=2, hedge=False, health_every=1), ECFG,
        monitor=monitor, injector=inj,
    )
    reqs = [_mk(cfg, i, 5, 8, seed=50 + i) for i in range(4)]
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["probe_failures"] >= 1
    assert fleet.replicas[0].state == "dead"
    assert fleet.stats["probes"] >= 2  # healthy replicas kept probing clean


def test_transient_probe_failure_needs_consecutive_breaches(gemma):
    """Regression: with ``consecutive_breaches=2`` a single corrupted health
    probe is treated as transient — the replica records the breach but stays
    live, and the next clean probe resets the streak."""
    cfg, params = gemma
    batch = {"tokens": jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(9), (1, 8), 0, cfg.vocab_size))}
    monitor = HealthMonitor(cfg, params, batch,
                            HealthConfig(consecutive_breaches=2))
    inj = FaultInjector()
    inj.corrupt_probe(0, at_step=1, probes=1)
    fleet = Fleet(
        cfg, params,
        FleetConfig(n_replicas=2, hedge=False, health_every=1), ECFG,
        monitor=monitor, injector=inj,
    )
    reqs = [_mk(cfg, i, 5, 8, seed=50 + i) for i in range(4)]
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["probe_failures"] == 1
    assert fleet.stats["kills"] == 0
    assert fleet.replicas[0].state == "live"  # survived the transient
    assert fleet.replicas[0].probe_breaches <= 1
    fleet._check_health(fleet._now + 1.0)  # one clean probe resets the streak
    assert fleet.replicas[0].probe_breaches == 0
    assert fleet.replicas[0].state == "live"


def test_storm_chaos_hits_integrity_pool_and_scrub_recovers(gemma):
    """Mid-trace fault-storm chaos lands on the replica's integrity-enabled
    pool; token streams are untouched (chaos never changes tokens) and the
    scrub/repair loop restores a bit-exact pool read."""
    from repro.core.integrity import IntegrityConfig
    from repro.core.planner import CrossbarSpec, PlannerConfig, _analyze_tensor_pool
    from repro.core.pool import CrossbarPool

    cfg, params = gemma
    spec = CrossbarSpec(rows=64, cols=8)
    pool = CrossbarPool(spec, 4, leveling="lpt")
    mgr = pool.enable_integrity(IntegrityConfig(spare_cols=2))
    w = jax.random.normal(jax.random.PRNGKey(0), (40, 20)) * 0.05
    _analyze_tensor_pool(w, spec, PlannerConfig(p_stuck=1.0, crossbars=4),
                         jax.random.PRNGKey(1), pool, name="t0")
    inj = FaultInjector()
    inj.storm(0, at_step=1, corrupt=5e-3, stuck=1e-3)
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=2, hedge=False), ECFG,
                  pools=[pool, None], injector=inj)
    reqs = [_mk(cfg, i, 5, 6, seed=60 + i) for i in range(3)]
    results = fleet.run(reqs)
    _assert_parity(cfg, params, fleet, reqs, results)
    assert fleet.stats["storms"] == 1 and inj.log[0]["kind"] == "storm"
    assert not mgr.verify_all()  # the storm really corrupted the pool
    rep = mgr.scrub_until_clean()
    assert rep.detections > 0 and mgr.verify_all() and mgr.clean


def test_mid_repair_replica_routed_around(gemma):
    """A replica whose scrubber holds pending (detected, budget-deferred)
    faults is excluded from placement while a healthy peer exists."""
    from repro.core.integrity import IntegrityConfig, tile_checksums
    from repro.core.planner import CrossbarSpec, PlannerConfig, _analyze_tensor_pool
    from repro.core.pool import CrossbarPool

    cfg, params = gemma
    spec = CrossbarSpec(rows=64, cols=8)
    pool = CrossbarPool(spec, 4, leveling="lpt")
    mgr = pool.enable_integrity(IntegrityConfig(spare_cols=4, repair_budget=1))
    w = jax.random.normal(jax.random.PRNGKey(0), (40, 20)) * 0.05
    _analyze_tensor_pool(w, spec, PlannerConfig(p_stuck=1.0, crossbars=4),
                         jax.random.PRNGKey(1), pool, name="t0")
    rec = mgr.tensors["t0"]
    # two hard faults in section 0's two most-populated columns: each remap
    # then costs writes, so budget=1 defers the second repair (a remap of
    # an all-zero column is free and would not spend the budget)
    pop = np.unpackbits(rec.expected[0], axis=0).sum(axis=0).astype(np.int64)
    faulty = np.argsort(-pop, kind="stable")[:2]
    assert pop[faulty].min() > 1
    for c in faulty:
        rec.stuck1[0, 0, c] |= 0x80
        for arr in (rec.expected, rec.reference, rec.stored):
            arr[0, 0, c] &= 0x7F
    rec.checksums[0] = tile_checksums(rec.expected[0:1], mgr.cfg.tile_bytes)[0]
    if rec.parity is not None:
        rec.parity[0] = np.bitwise_xor.reduce(rec.expected[0], axis=1)
    mgr.scrub_round()
    assert mgr.pending_faults() > 0
    fleet = Fleet(cfg, params, FleetConfig(n_replicas=2, hedge=False), ECFG,
                  pools=[pool, None])
    assert fleet.replicas[0].mid_repair()
    # pending faults price into the score AND exclude the replica outright
    assert fleet.replicas[0].score(fleet.fcfg) >= fleet.fcfg.w_scrub
    req = _mk(cfg, 0, 5, 4, seed=7)
    res = fleet.run([req])
    assert res[0].replica == 1
    assert res[0].tokens == _solo(cfg, params, req)
    # once the scrubber converges the replica is placeable again
    mgr.scrub_until_clean()
    assert not fleet.replicas[0].mid_repair()


# ---------------------------------------------------------------------------
# Placement scoring
# ---------------------------------------------------------------------------

def test_placement_prefers_unworn_unfaulted_replica(gemma):
    """Wear/fault-aware placement: a replica whose pool is nearly exhausted
    (finite endurance horizon) and fault-ridden scores worse than a pristine
    one, so single requests route to the healthy replica."""
    from repro.core import nonideal
    from repro.core.planner import CrossbarSpec
    from repro.core.pool import CrossbarPool

    cfg, params = gemma
    worn = CrossbarPool(CrossbarSpec(rows=64, cols=8), 4)
    worn.wear[:] = 10**7  # deep into the endurance budget
    worn.inject_faults(nonideal.FaultModel(stuck0=0.02, stuck1=0.02),
                       jax.random.PRNGKey(0))
    fresh = CrossbarPool(CrossbarSpec(rows=64, cols=8), 4)
    fleet = Fleet(
        cfg, params, FleetConfig(n_replicas=2, hedge=False), ECFG,
        pools=[worn, fresh],
    )
    assert fleet.replicas[0].score(fleet.fcfg) > fleet.replicas[1].score(fleet.fcfg)
    req = _mk(cfg, 0, 5, 4, seed=7)
    res = fleet.run([req])
    assert res[0].replica == 1  # routed away from the worn pool
    assert res[0].tokens == _solo(cfg, params, req)


def test_pools_length_mismatch_rejected(gemma):
    cfg, params = gemma
    with pytest.raises(ValueError, match="one entry per replica"):
        Fleet(cfg, params, FleetConfig(n_replicas=2), ECFG, pools=[None])


# ---------------------------------------------------------------------------
# Retry budget
# ---------------------------------------------------------------------------

def test_retry_budget_exhaustion_sheds(gemma):
    """A request that loses its replica more times than the retry budget is
    shed rather than bounced forever."""
    cfg, params = gemma
    fleet = Fleet(
        cfg, params,
        FleetConfig(n_replicas=2, hedge=False,
                    retry=FaultPolicy(max_retries=1, backoff_s=0.0)),
        ECFG,
    )
    req = _mk(cfg, 0, 5, 48, seed=0)
    fleet.submit(req)
    fleet.step(0.0)
    fleet.kill(0, 0.1)  # placement 1 lost
    fleet.step(0.2)     # re-placed on replica 1 (placement 2 = max)
    fleet.kill(1, 0.3)  # placement 2 lost -> budget spent -> shed
    fleet.step(0.4)
    assert fleet.results[0].status == "shed"
    assert fleet.stats["shed"] == 1
