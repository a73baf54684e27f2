"""Tensor-parallel sharded replicas (parallel/tp.py + launch plumbing).

The battery pins the TP contract end to end:

(1) *plan law* — ``plan_tp`` never errors on ragged head/ff counts: an
    indivisible component degrades to replication with the reason recorded,
    and ``local_config`` divides exactly what the plan sharded (property
    tests drive arbitrary head/kv/ff combinations through the fallback);
(2) *operand slicing is exact* — ``simulator.shard_operands`` slices stored
    bit planes such that densify∘shard == shard∘densify byte-for-byte, and
    dense leaves concatenate back to the global tensor;
(3) *serving parity* — ``tp_generate`` token streams match solo
    single-device ``serve.generate`` at shard counts {1, 2, 4} for dense,
    packed/raw and packed/col_perm materializations (bit-identical at n=1:
    psum over a 1-shard axis is the identity), and ``Engine(tp=...)`` holds
    the same parity through ragged mixed-sampling traffic and swap
    preemption;
(4) *pool partition* — ``build_sharded_deployment`` reproduces the global
    deployment bit-exactly (same per-tensor PRNG schedule) and, under
    per-tensor pristine accounting, the summed wear of the shard pools
    equals the unsharded pool's wear exactly (conservation);
(5) *scrub under sharding* — ``ShardedScrub`` repairs a deterministic storm
    across per-shard pools between engine dispatches without stalling the
    replica, and post-refresh tokens match the clean deployment;
(6) *mesh carve-up* — ``replica_submeshes`` groups are contiguous on the
    model axis, warn-and-emulate on one CPU device, and reject
    non-contiguous wrap-around; on TPU, a replica's shards must sit on
    distinct chips (``replica_submeshes`` and ``Engine(tp=...)`` raise).

The native ``shard_map`` path (real N-device mesh) is pinned by a
subprocess test under ``--xla_force_host_platform_device_count=4`` (marked
slow; the multi-device CI job also runs the in-process ``skipif``-gated
variant) together with the ``sws.stable_argsort`` routing regression:
emulated devices must not flip the host-callback guard.
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.configs import get_arch
from repro.core import simulator
from repro.core.integrity import IntegrityConfig
from repro.core.planner import (
    CrossbarSpec,
    PlannerConfig,
    build_deployment,
    deploy_params,
)
from repro.core.pool import CrossbarPool
from repro.launch.engine import Engine, EngineConfig, Request
from repro.launch.fleet import Fleet, FleetConfig
from repro.launch.mesh import replica_submeshes
from repro.launch.serve import generate
from repro.models import api
from repro.parallel import tp
from repro.parallel.tp import (
    ShardedScrub,
    build_sharded_deployment,
    local_config,
    plan_tp,
    shard_params,
    tp_generate,
)

ECFG = EngineConfig(
    max_slots=2, page_size=8, max_seq_len=64, prefill_chunk=8, decode_quantum=4
)
LM_SPEC = CrossbarSpec(rows=128, cols=10)
LM_CFG = PlannerConfig(p_stuck=0.5, min_size=1024)


@pytest.fixture(scope="module")
def lm():
    """internlm2 reduced: 4 heads / 2 KV heads / d_ff=128 — shardable at 2,
    attention-fallback (kv 2 % 4) at 4."""
    cfg = get_arch("internlm2-1.8b", reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_requests(cfg, specs, rid0=0, greedy=True):
    out = []
    for i, (plen, gen) in enumerate(specs):
        rid = rid0 + i
        prompt = np.asarray(
            jax.random.randint(jax.random.PRNGKey(100 + rid), (plen,), 0, cfg.vocab_size)
        )
        out.append(Request(rid=rid, prompt=prompt, max_new_tokens=gen,
                           greedy=greedy, seed=rid))
    return out


def _solo(cfg, params, req):
    batch = {"tokens": jnp.asarray(req.prompt)[None]}
    toks, _ = generate(cfg, params, batch, gen_len=req.max_new_tokens,
                       greedy=req.greedy, seed=req.seed)
    return [int(t) for t in np.asarray(toks[0])]


# ---------------------------------------------------------------------------
# (6) mesh carve-up
# ---------------------------------------------------------------------------

def test_replica_submeshes_contiguous_groups(monkeypatch):
    fake = [object() for _ in range(4)]
    monkeypatch.setattr(jax, "devices", lambda: list(fake))
    assert replica_submeshes(2, 2) == [[fake[0], fake[1]], [fake[2], fake[3]]]
    assert replica_submeshes(1, 4) == [fake]
    # spr == 1 wraps silently over the available devices (PR 8 behavior)
    assert replica_submeshes(6, 1) == [[fake[i % 4]] for i in range(6)]
    # a full lap is fine: replica 2 restarts at device 0, still contiguous
    assert replica_submeshes(3, 2)[2] == [fake[0], fake[1]]


def test_replica_submeshes_rejects_noncontiguous_wrap(monkeypatch):
    fake = [object() for _ in range(4)]
    monkeypatch.setattr(jax, "devices", lambda: list(fake))
    # replica 1 would start at device 3 and need devices {3, 0, 1}
    with pytest.raises(ValueError, match="non-contiguously"):
        replica_submeshes(2, 3)


def test_replica_submeshes_single_device_emulates_with_warning():
    assert len(jax.devices()) == 1  # the tier-1 contract the module relies on
    with pytest.warns(UserWarning, match="vmap-emulated"):
        groups = replica_submeshes(2, 4)
    assert groups == [[jax.devices()[0]] * 4] * 2


def test_replica_submeshes_validation():
    with pytest.raises(ValueError):
        replica_submeshes(0, 1)
    with pytest.raises(ValueError):
        replica_submeshes(1, 0)


def test_replica_submeshes_single_tpu_device_raises(monkeypatch):
    """On a TPU, shards never fall back to sharing one chip."""
    import repro.launch.mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="needs 4 TPU devices"):
        replica_submeshes(2, 4)


@pytest.mark.parametrize("devices", ["missing", "repeated"])
def test_engine_tp_on_tpu_requires_distinct_devices(lm, monkeypatch, devices):
    """Engine(tp>1) on TPU raises where the CPU route would vmap-emulate."""
    import repro.launch.engine as engine_mod

    cfg, params = lm
    monkeypatch.setattr(engine_mod, "on_tpu", lambda: True)
    devs = None if devices == "missing" else [jax.devices()[0]] * 2
    with pytest.raises(ValueError, match="2 distinct tp_devices"):
        Engine(cfg, params, ECFG, tp=2, tp_devices=devs)


# ---------------------------------------------------------------------------
# (1) plan law
# ---------------------------------------------------------------------------

def test_plan_tp_shards_both_components(lm):
    cfg, _ = lm
    plan = plan_tp(cfg, 2, packed=True)
    assert plan.attn and plan.mlp
    assert plan.rules["attn/wq"] == -1 and plan.rules["attn/wo"] == -2
    assert plan.rules["mlp/wi_gate"] == -1 and plan.rules["mlp/wo"] == -2
    loc = local_config(cfg, plan)
    assert (loc.n_heads, loc.n_kv_heads, loc.d_ff) == (2, 1, 64)
    assert loc.resolved_head_dim == cfg.resolved_head_dim  # pinned, not re-derived
    assert loc.tp_attn and loc.tp_mlp and loc.tp_axis == plan.axis


def test_plan_tp_attention_fallback_keeps_mlp(lm):
    cfg, _ = lm
    plan = plan_tp(cfg, 4, packed=True)
    assert not plan.attn and plan.mlp
    assert "n_kv_heads 2 % 4" in plan.reasons["attn"]
    loc = local_config(cfg, plan)
    assert loc.n_heads == cfg.n_heads and loc.d_ff == 32
    assert not loc.tp_attn and loc.tp_mlp


def test_plan_tp_mqa_replicates_attention():
    cfg = get_arch("gemma-2b", reduced=True)  # MQA: one KV head
    plan = plan_tp(cfg, 2)
    assert not plan.attn and "n_kv_heads 1 % 2" in plan.reasons["attn"]


def test_plan_tp_foreign_block_kinds_replicate_everything():
    cfg = get_arch("xlstm-350m", reduced=True)
    plan = plan_tp(cfg, 2)
    assert not plan.attn and not plan.mlp and not plan.rules
    assert "no TP reduction gates" in plan.reasons["attn"]


def test_plan_tp_packed_byte_alignment_gate(lm):
    cfg, _ = lm
    # head_dim 16: dense 2-way slice of wo's K axis is 32 rows (byte-aligned),
    # but head_dim 4 would make it 8... shrink to force the packed-only gate:
    ragged = dataclasses.replace(cfg, head_dim=1)
    assert plan_tp(ragged, 2, packed=False).attn
    plan = plan_tp(ragged, 2, packed=True)
    assert not plan.attn and "byte-aligned" in plan.reasons["attn"]


@given(
    n_heads=st.sampled_from([1, 2, 3, 4, 6, 8]),
    kv_div=st.sampled_from([1, 2, 4]),
    head_dim=st.sampled_from([4, 8, 16]),
    d_ff=st.sampled_from([24, 32, 48, 64, 120, 128]),
    n=st.integers(min_value=1, max_value=5),
    packed=st.booleans(),
)
def test_plan_tp_fallback_law(n_heads, kv_div, head_dim, d_ff, n, packed):
    """Any head/kv/ff combination plans without error; sharded components
    divide exactly and replicated ones record why."""
    if n_heads % kv_div:
        kv_div = 1
    base = get_arch("internlm2-1.8b", reduced=True)
    cfg = dataclasses.replace(
        base, n_heads=n_heads, n_kv_heads=n_heads // kv_div,
        head_dim=head_dim, d_ff=d_ff,
    )
    plan = plan_tp(cfg, n, packed=packed)
    loc = local_config(cfg, plan)
    if plan.attn:
        assert cfg.n_heads % n == 0 and cfg.n_kv_heads % n == 0
        assert loc.n_heads * n == cfg.n_heads
        assert loc.n_kv_heads * n == cfg.n_kv_heads
        if packed:
            assert (loc.n_heads * head_dim) % 8 == 0
    elif n > 1:
        assert "attn" in plan.reasons
    if plan.mlp:
        assert loc.d_ff * n == cfg.d_ff
        if packed:
            assert loc.d_ff % 8 == 0
    elif n > 1:
        assert "mlp" in plan.reasons


# ---------------------------------------------------------------------------
# (2) operand slicing exactness
# ---------------------------------------------------------------------------

def _rand_operands(key, k, n_cols, codec="raw"):
    w = jax.random.normal(key, (k, n_cols)) * 0.05
    scale = float(jnp.max(jnp.abs(w))) / (2**4 - 1)
    q = jnp.clip(jnp.round(jnp.abs(w) / scale), 0, 15).astype(jnp.int32)
    sign = jnp.where(jnp.signbit(w), -1, 1).astype(jnp.int8)
    op = simulator.packed_operands(q, sign, scale, 0.0, 4)
    if codec != "raw":
        from repro.core import planes

        op = planes.encode_operands(op, codec)
    return op


@given(
    k8=st.integers(min_value=1, max_value=6),
    cols=st.sampled_from([4, 6, 8, 12]),
    n=st.sampled_from([2, 3, 4]),
    axis=st.sampled_from([-1, -2]),
    codec=st.sampled_from(["raw", "col_perm"]),
)
def test_shard_operands_exact(k8, cols, n, axis, codec):
    """densify(shard(op)) == shard(densify(op)) byte-for-byte, both axes."""
    size = cols if axis == -1 else k8 * 8
    if size % n or (axis == -2 and ((size // n) % 8)):
        return  # indivisible draws are plan_tp's job, not shard_operands'
    op = _rand_operands(jax.random.PRNGKey(k8 * 100 + cols), k8 * 8, cols, codec)
    dense = np.asarray(simulator.densify_operands(op))
    shards = [simulator.shard_operands(op, axis=axis, index=i, n=n) for i in range(n)]
    step = size // n
    for i, sh in enumerate(shards):
        sl = [slice(None)] * 2
        sl[axis] = slice(i * step, (i + 1) * step)
        np.testing.assert_array_equal(
            np.asarray(simulator.densify_operands(sh)), dense[tuple(sl)]
        )


def test_shard_operands_rejects_misaligned_k_slice():
    op = _rand_operands(jax.random.PRNGKey(0), 16, 4)
    with pytest.raises(ValueError, match="byte"):
        simulator.shard_operands(op, axis=-2, index=0, n=4)  # 4-row slices
    with pytest.raises(ValueError):
        simulator.shard_operands(op, axis=-1, index=2, n=2)  # index range
    with pytest.raises(ValueError):
        simulator.shard_operands(op, axis=-1, index=0, n=3)  # 4 % 3


@given(
    n=st.sampled_from([1, 2, 4]),
    heads=st.sampled_from([4, 8]),
    d_ff=st.sampled_from([32, 64]),
)
def test_shard_params_concat_roundtrip(n, heads, d_ff):
    """Per-leaf shard shapes multiply back: concatenating every shard on its
    rule axis reproduces the dense leaf; replicated leaves are shared."""
    base = get_arch("internlm2-1.8b", reduced=True)
    cfg = dataclasses.replace(
        base, n_heads=heads, n_kv_heads=heads // 2, head_dim=8, d_ff=d_ff
    )
    hd = cfg.resolved_head_dim
    key = jax.random.PRNGKey(7)
    tree = {
        "segments": {
            "0": {
                "attn": {
                    "wq": jax.random.normal(key, (2, cfg.d_model, heads * hd)),
                    "wo": jax.random.normal(key, (2, heads * hd, cfg.d_model)),
                },
                "mlp": {
                    "wi_gate": jax.random.normal(key, (2, cfg.d_model, d_ff)),
                    "wo": jax.random.normal(key, (2, d_ff, cfg.d_model)),
                },
                "norm": {"w": jax.random.normal(key, (2, cfg.d_model))},
            }
        }
    }
    plan = plan_tp(cfg, n)
    shards = [shard_params(tree, plan, i) for i in range(n)]
    flat_ref = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, ref in flat_ref:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        pieces = []
        for s in shards:
            cur = s
            for part in name.split("/"):
                cur = cur[part]
            pieces.append(np.asarray(cur))
        ax = tp._leaf_rule(name, plan)
        if ax is None or n == 1:
            for p in pieces:
                np.testing.assert_array_equal(p, np.asarray(ref))
        else:
            np.testing.assert_array_equal(
                np.concatenate(pieces, axis=ax), np.asarray(ref)
            )


# ---------------------------------------------------------------------------
# (3) serving parity: tp_generate and Engine(tp=...)
# ---------------------------------------------------------------------------

def _deployed(lm, materialize, codec):
    cfg, params = lm
    if materialize == "dense" and codec is None:
        return params
    plan = build_deployment(params, LM_SPEC, LM_CFG)
    return deploy_params(params, plan, materialize=materialize,
                         codec=codec or "raw")


@pytest.mark.parametrize(
    "materialize,codec",
    [("dense", None), ("packed", "raw"), ("packed", "col_perm")],
    ids=["dense", "packed-raw", "packed-colperm"],
)
def test_tp_generate_parity(lm, materialize, codec):
    """Token streams at shard counts {1, 2, 4} match solo serve.generate for
    every materialization; n=1 is bit-identical (psum is the identity)."""
    cfg, _ = lm
    served = _deployed(lm, materialize, codec)
    batch = {"tokens": jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (2, 7), 0, cfg.vocab_size)
    )}
    ref, _ = generate(cfg, served, batch, gen_len=6)
    ref = np.asarray(ref)
    for n in (1, 2, 4):
        toks, tps = tp_generate(cfg, served, batch, n=n, gen_len=6)
        np.testing.assert_array_equal(np.asarray(toks), ref, err_msg=f"n={n}")
        assert tps > 0


def test_tp_generate_sampled_parity(lm):
    """The sampled path shares solo's PRNG schedule shard-for-shard."""
    cfg, params = lm
    batch = {"tokens": jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (1, 5), 0, cfg.vocab_size)
    )}
    ref, _ = generate(cfg, params, batch, gen_len=5, greedy=False, seed=9)
    toks, _ = tp_generate(cfg, params, batch, n=2, gen_len=5, greedy=False, seed=9)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))


@pytest.mark.parametrize("n", [2, 4])
def test_engine_tp_parity_mixed_traffic(lm, n):
    """Engine(tp=n) serves ragged greedy+sampled traffic bit-identical to the
    unsharded solo pipeline; host scheduler shapes are untouched."""
    cfg, params = lm
    eng = Engine(cfg, params, ECFG, tp=n)
    reqs = _mk_requests(cfg, [(11, 6), (5, 8), (8, 5)], greedy=True)
    reqs += _mk_requests(cfg, [(6, 6)], rid0=3, greedy=False)
    results = eng.run(reqs)
    for req, res in zip(reqs, results):
        assert res.status == "ok"
        assert res.tokens == _solo(cfg, params, req), f"rid {req.rid}"


def test_engine_tp_swap_preemption_parity(lm):
    """Preemption swaps per-shard paged pools (leading shard axis) out and
    back byte-identically: the -3 cell-axis indexing in paged_cache."""
    cfg, params = lm
    ecfg = dataclasses.replace(ECFG, num_blocks=7)
    eng = Engine(cfg, params, ecfg, tp=2)
    reqs = _mk_requests(cfg, [(14, 18), (13, 18)])
    results = eng.run(reqs)
    assert eng.stats["preemptions"] >= 1 and eng.stats["swap_ins"] >= 1
    for req, res in zip(reqs, results):
        assert res.status == "ok"
        assert res.tokens == _solo(cfg, params, req), f"rid {req.rid}"


def test_engine_tp_dispatch_from_requires_matching_plan(lm):
    cfg, params = lm
    donor = Engine(cfg, params, ECFG, tp=2)
    clone = Engine(cfg, params, ECFG, tp=2, dispatch_from=donor)
    assert clone._tp == donor._tp
    with pytest.raises(ValueError, match="dispatch_from"):
        Engine(cfg, params, ECFG, tp=4, dispatch_from=donor)


def test_fleet_sharded_replicas_parity(lm):
    """shards_per_replica plumbs through Fleet -> Replica -> Engine(tp=...);
    routing over shards-of-meshes keeps every stream solo-identical."""
    cfg, params = lm
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # single-device vmap emulation
        fleet = Fleet(
            cfg, params,
            FleetConfig(n_replicas=2, shards_per_replica=2, hedge=False), ECFG,
        )
    assert all(len(r.devices) == 2 for r in fleet.replicas)
    reqs = _mk_requests(cfg, [(5, 6), (7, 5), (6, 6), (9, 4)])
    results = fleet.run(reqs)
    for req, res in zip(reqs, results):
        assert res.status == "ok"
        assert res.tokens == _solo(cfg, params, req), f"rid {req.rid}"
    assert {r.replica for r in results} == {0, 1}


# ---------------------------------------------------------------------------
# (4) sharded pools: plan parity + wear conservation
# ---------------------------------------------------------------------------

class _PristinePool(CrossbarPool):
    """Per-tensor pristine accounting: content resets before every tensor's
    program, wear survives — the planner's parity invariant (a)."""

    def program(self, *args, **kwargs):
        self.reset()
        return super().program(*args, **kwargs)


def test_sharded_deployment_plan_matches_global(lm):
    """Round-robin tensor partitioning with the GLOBAL per-tensor PRNG
    schedule: under pristine per-tensor accounting every deployed w_hat is
    bit-identical to the unsharded (stateless) plan.  (Persistent pools
    diverge by design — each tensor reprograms over a different
    cross-tensor seam than in the unsharded stream.)"""
    cfg, params = lm
    ref = build_deployment(params, LM_SPEC, LM_CFG)
    plan, pools, owner = build_sharded_deployment(
        params, LM_SPEC, LM_CFG, 2,
        pools=[_PristinePool(LM_SPEC, LM_CFG.crossbars) for _ in range(2)],
    )
    assert set(plan.deployed) == set(ref.deployed)
    assert set(owner.values()) == {0, 1}
    for name in ref.deployed:
        np.testing.assert_array_equal(
            np.asarray(plan.deployed[name]), np.asarray(ref.deployed[name]),
            err_msg=name,
        )
    # deploy_params accepts the merged plan unchanged
    served = deploy_params(params, plan, materialize="dense")
    ref_served = deploy_params(params, ref, materialize="dense")
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(ref_served)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [2, 3])
def test_sharded_pool_wear_conservation(lm, n):
    """Under per-tensor pristine accounting the shard pools' summed wear
    equals the unsharded pool's — partitioning storage loses no writes."""
    cfg, params = lm
    solo_pool = _PristinePool(LM_SPEC, LM_CFG.crossbars)
    build_deployment(params, LM_SPEC, LM_CFG, pool=solo_pool)
    shard_pools = [_PristinePool(LM_SPEC, LM_CFG.crossbars) for _ in range(n)]
    _, shard_pools, owner = build_sharded_deployment(
        params, LM_SPEC, LM_CFG, n, pools=shard_pools
    )
    total = sum(int(p.wear.sum()) for p in shard_pools)
    assert total == int(solo_pool.wear.sum())
    assert sum(p.tensors_seen for p in shard_pools) == solo_pool.tensors_seen
    assert len(owner) == solo_pool.tensors_seen


# ---------------------------------------------------------------------------
# (5) scrub under sharding
# ---------------------------------------------------------------------------

def test_sharded_scrub_storm_repairs_with_token_parity(lm):
    """A deterministic storm across per-shard pools: the round-robin budget
    lets every shard progress each round (no shard starves the others), the
    merged report sums pending across pools, and the refreshed engine serves
    bit-identical to the clean deployment."""
    cfg, params = lm
    pools = [
        CrossbarPool(LM_SPEC, LM_CFG.crossbars, leveling="lpt") for _ in range(2)
    ]
    mgrs = [
        p.enable_integrity(IntegrityConfig(spare_cols=2, scrub_tiles=1_000_000))
        for p in pools
    ]
    plan, pools, owner = build_sharded_deployment(
        params, LM_SPEC, LM_CFG, 2, pools=pools
    )
    clean = deploy_params(params, plan, materialize="dense")
    scrub = ShardedScrub(mgrs)

    eng = Engine(cfg, clean, ECFG, tp=2)
    eng.attach_scrub(
        scrub,
        refresh=lambda: deploy_params(
            params, scrub.rebuild_plan(plan), materialize="dense"
        ),
    )
    # storm BOTH pools: a mid-repair shard must not stall its peer's scan
    mgrs[0].storm(jax.random.PRNGKey(11), corrupt_rate=2e-3, stuck_rate=2e-4)
    mgrs[1].storm(jax.random.PRNGKey(12), corrupt_rate=2e-3, stuck_rate=2e-4)
    assert scrub.pending_faults() == 0  # undetected until a scrub round runs
    corrupted = deploy_params(params, scrub.rebuild_plan(plan), materialize="dense")
    assert eng.hot_swap(corrupted)
    eng.run(_mk_requests(cfg, [(11, 5), (7, 6)]))
    assert eng.stats["scrub_rounds"] > 0
    assert eng.stats["scrub_detections"] > 0
    assert eng.stats["scrub_repairs"] > 0
    assert eng.stats["scrub_refreshes"] >= 1
    assert scrub.verify_all() and scrub.pending_faults() == 0
    post = _mk_requests(cfg, [(9, 6)], rid0=10)
    res = eng.run(post)[0]
    assert res.tokens == _solo(cfg, clean, post[0])


def test_sharded_scrub_splits_round_budget():
    class _FakeMgr:
        def __init__(self):
            self.budgets = []

        def pending_faults(self):
            return 1

        def scrub_round(self, budget_tiles=None):
            self.budgets.append(budget_tiles)
            return dataclasses.make_dataclass(
                "R", ["pending"], namespace={
                    "merge": lambda self, other: None
                }
            )(pending=2)

    mgrs = [_FakeMgr() for _ in range(3)]
    scrub = ShardedScrub(mgrs)
    rep = scrub.scrub_round(budget_tiles=9)
    assert all(m.budgets == [3] for m in mgrs)  # 9 // 3 each, every shard ran
    assert rep.pending == 6  # summed across pools, not last-round-wins
    assert scrub.pending_faults() == 3
    with pytest.raises(ValueError):
        ShardedScrub([])


# ---------------------------------------------------------------------------
# native shard_map path + stable_argsort routing under an emulated mesh
# ---------------------------------------------------------------------------

_NATIVE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    assert jax.device_count() == 4

    # sws routing regression: emulated devices add execution streams, not
    # host cores — the host-callback guard must key on cores alone, and the
    # sort must stay correct either way.
    from repro.core import sws
    assert sws._use_host_sort() == (sws._usable_cores() > 1)
    keys = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    perm, inv = sws.stable_argsort(keys, with_inverse=True)
    kk = np.asarray(keys)
    np.testing.assert_array_equal(np.asarray(perm), np.argsort(kk, kind="stable"))
    np.testing.assert_array_equal(np.asarray(inv)[np.asarray(perm)], np.arange(4096))

    from repro.configs import get_arch
    from repro.launch.serve import generate
    from repro.models import api
    from repro.parallel.tp import tp_generate

    cfg = get_arch("internlm2-1.8b", reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, cfg.vocab_size)}
    ref, _ = generate(cfg, params, batch, gen_len=5)
    for n in (2, 4):
        toks, _ = tp_generate(cfg, params, batch, n=n, gen_len=5,
                              devices=jax.devices()[:n])
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))
    print("TP_NATIVE_OK")
    """
)


@pytest.mark.slow  # fresh 4-device interpreter: jit from cold
def test_tp_native_shard_map_subprocess():
    env = dict(os.environ, PYTHONPATH="src", XLA_FLAGS="")
    out = subprocess.run(
        [sys.executable, "-c", _NATIVE_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TP_NATIVE_OK" in out.stdout


@pytest.mark.skipif(jax.device_count() < 4, reason="needs a 4-device mesh")
def test_tp_native_shard_map_parity(lm):
    """In-process native-mesh parity — runs in the multi-device CI job
    (XLA_FLAGS set before pytest), skips on the tier-1 single device."""
    cfg, params = lm
    batch = {"tokens": jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, cfg.vocab_size)
    )}
    ref, _ = generate(cfg, params, batch, gen_len=5)
    for n in (2, 4):
        toks, _ = tp_generate(
            cfg, params, batch, n=n, gen_len=5, devices=jax.devices()[:n]
        )
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))
