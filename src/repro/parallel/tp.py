"""Tensor-parallel sharding of one serving replica over a "model" mesh axis.

One engine replica (or one `serve.generate` pipeline) is split N ways with
the Megatron column/row-parallel discipline, derived from the SAME rule
table serving already trusts for training layouts (`parallel.sharding`):

  * ``wq`` / ``wk`` / ``wv`` / ``wi_gate`` / ``wi_up`` — column-parallel
    (output axis sliced; each shard owns ``n_heads/N`` heads and ``d_ff/N``
    hidden channels, so attention and the GLU nonlinearity stay shard-local),
  * ``attn/wo`` / ``mlp/wo`` — row-parallel (contraction axis sliced; each
    shard holds a PARTIAL output, summed with ``lax.psum`` before the
    residual add — the gated reduction points in ``models.blocks``),
  * embeddings / norms / ``head`` — replicated.  The rule table shards the
    vocab axis for training, but serving samples from the logits on the
    host, so the head stays replicated here and every shard finishes each
    layer (and the unembedding) with FULL activations.  Token sampling is
    therefore identical on every shard and the engine's host-side scheduler
    needs no changes.

Packed CIM operands shard by *slicing the stored bit planes* — see
``simulator.shard_operands`` — never by requantizing, so the dense and
packed layouts of one tensor agree shard-by-shard by construction
(``densify(shard(op)) == shard(densify(op))`` byte-for-byte).  The paged KV
pool partitions on the head axis for free: each shard's ``wk``/``wv`` slice
only ever *produces* its own ``n_kv_heads/N`` heads, so per-shard pools are
just the local-config pools stacked on a leading shard axis, sharing ONE
block table / slot schedule.

Execution: the shard axis is a *leading pytree axis*.  ``_spmd`` runs the
unmodified single-shard step either under ``jax.vmap`` with a bound
``axis_name`` (single-device emulation: ``lax.psum`` reduces over the vmap
axis — this is how the parity battery pins {1, 2, 4}-way sharding on one
CPU device) or under ``shard_map`` over a real ``Mesh`` of N devices (the
host-emulated ``--xla_force_host_platform_device_count`` mesh or real
accelerators), where the same psum lowers to an all-reduce.  Both paths run
the SAME jitted step functions with the same signatures as their unsharded
twins, so `launch.engine` only swaps the wrapper in.

Divisibility is checked per *component*, not per leaf: GQA/MQA means
``n_kv_heads`` can refuse a split that every leaf shape would accept (gemma
reduced holds one KV head — slicing ``wk``'s 32 columns 2-ways would cut
mid-head).  A component that cannot split degrades to replication (the
plan records why), never an error — the property-test battery drives
ragged head/column counts through this fallback.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import simulator
from repro.parallel import sharding as shrules

DEFAULT_AXIS = "model"

# component membership: the trailing "<sublayer>/<leaf>" of a param path.
# Directions (col = slice output axis -1, row = slice contraction axis -2)
# are cross-checked against sharding._RULES in plan_tp, not hard-coded
# trust: if the rule table ever disagrees, the component replicates.
_ATTN_LEAVES = {"wq": -1, "wk": -1, "wv": -1, "wo": -2}
_MLP_LEAVES = {"wi_gate": -1, "wi_up": -1, "wo": -2}
_ATTN_SUBLAYERS = ("attn", "self", "cross")
_MLP_SUBLAYERS = ("mlp", "shared")
_TP_KINDS = {"attn", "swa"}  # block kinds with psum gates (models.blocks)


@dataclasses.dataclass(frozen=True)
class TPPlan:
    """How one replica splits over ``n`` shards of mesh axis ``axis``.

    ``attn`` / ``mlp``: whether that component is sharded (False =
    replicated on every shard; the matching psum is disabled so replicated
    partial sums are not double-counted).  ``rules`` maps a component-
    qualified leaf suffix (``"attn/wo"``) to its slice axis; ``reasons``
    records why a component degraded to replication.
    """

    n: int
    axis: str = DEFAULT_AXIS
    attn: bool = False
    mlp: bool = False
    rules: Mapping[str, int] = dataclasses.field(default_factory=dict)
    reasons: Mapping[str, str] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.n < 1:
            raise ValueError(f"shard count must be >= 1, got {self.n}")


def _rule_axis(name: str, stacked_shape: tuple[int, ...], axis: str, n: int) -> Optional[int]:
    """Slice axis the `parallel.sharding` rule table assigns ``name``.

    Resolved against a representative stacked path (how serving param trees
    name their leaves) and mapped back to a negative axis so the same rule
    applies to 2-D and scan-stacked 3-D leaves alike.  None = the table
    replicates this leaf at this mesh size.
    """
    spec = shrules._resolve(name, stacked_shape, {axis: n}, fsdp=False, fsdp_min=2**62)
    entries = tuple(spec)
    if axis not in entries:
        return None
    return entries.index(axis) - len(entries)


def plan_tp(cfg: ArchConfig, n: int, *, packed: bool = False, axis: str = DEFAULT_AXIS) -> TPPlan:
    """Plan an ``n``-way tensor-parallel split of ``cfg``.

    Per-component constraints (checked before consulting the rule table —
    leaf shapes alone would happily cut a grouped-query head in half):

    * attention: ``n_heads % n == 0`` and ``n_kv_heads % n == 0``; packed
      operands additionally need the row-parallel ``wo`` contraction slice
      ``(n_heads // n) * head_dim`` byte-aligned (``% 8``), since bit planes
      pack 8 rows per byte and shards slice stored bytes, never repack.
    * mlp: ``d_ff % n == 0``; packed needs ``(d_ff // n) % 8 == 0``.

    A failing component is *replicated* (never an error) with the reason
    recorded — the divisibility fallback law the property tests pin.
    """
    if n < 1:
        raise ValueError(f"shard count must be >= 1, got {n}")
    reasons: dict[str, str] = {}
    hd = cfg.resolved_head_dim
    kinds = set(cfg.layer_kinds())
    if cfg.encdec or not kinds <= _TP_KINDS:
        why = f"block kinds {sorted(kinds)} have no TP reduction gates"
        return TPPlan(n=n, axis=axis, reasons={"attn": why, "mlp": why})

    attn, mlp = True, True
    if cfg.n_heads % n:
        attn, reasons["attn"] = False, f"n_heads {cfg.n_heads} % {n} != 0"
    elif cfg.n_kv_heads % n:
        attn, reasons["attn"] = False, f"n_kv_heads {cfg.n_kv_heads} % {n} != 0"
    elif packed and ((cfg.n_heads // n) * hd) % 8:
        attn, reasons["attn"] = False, (
            f"packed wo K-slice {(cfg.n_heads // n) * hd} not byte-aligned"
        )
    if cfg.d_ff % n:
        mlp, reasons["mlp"] = False, f"d_ff {cfg.d_ff} % {n} != 0"
    elif packed and (cfg.d_ff // n) % 8:
        mlp, reasons["mlp"] = False, f"packed mlp K-slice {cfg.d_ff // n} not byte-aligned"

    # derive each leaf's slice axis from the rule table; any disagreement
    # (e.g. an axis-swap fallback moving the mesh axis somewhere this slicer
    # does not model) replicates the whole component
    shapes = {
        "attn/wq": (cfg.d_model, cfg.n_heads * hd),
        "attn/wk": (cfg.d_model, cfg.n_kv_heads * hd),
        "attn/wv": (cfg.d_model, cfg.n_kv_heads * hd),
        "attn/wo": (cfg.n_heads * hd, cfg.d_model),
        "mlp/wi_gate": (cfg.d_model, cfg.d_ff),
        "mlp/wi_up": (cfg.d_model, cfg.d_ff),
        "mlp/wo": (cfg.d_ff, cfg.d_model),
    }
    rules: dict[str, int] = {}
    for comp, leaves, on in (("attn", _ATTN_LEAVES, attn), ("mlp", _MLP_LEAVES, mlp)):
        if not on:
            continue
        want = {f"{comp}/{leaf}": ax for leaf, ax in leaves.items()}
        got = {
            key: _rule_axis(f"segments/0/{key}", (cfg.n_layers, *shapes[key]), axis, n)
            for key in want
        }
        if got != want:
            bad = sorted(k for k in want if got[k] != want[k])
            reasons[comp] = f"rule table resolves {bad} differently at n={n}"
            if comp == "attn":
                attn = False
            else:
                mlp = False
        else:
            rules.update(want)
    return TPPlan(n=n, axis=axis, attn=attn, mlp=mlp, rules=rules, reasons=reasons)


def local_config(cfg: ArchConfig, plan: TPPlan) -> ArchConfig:
    """The ArchConfig ONE shard runs: divided head/ff counts + psum gates.

    ``head_dim`` is pinned explicitly — its ``d_model // n_heads`` default
    would silently double under a halved head count.
    """
    kw: dict[str, Any] = {
        "tp_axis": plan.axis if (plan.attn or plan.mlp) else None,
        "tp_attn": plan.attn,
        "tp_mlp": plan.mlp,
    }
    if plan.attn:
        kw.update(
            n_heads=cfg.n_heads // plan.n,
            n_kv_heads=cfg.n_kv_heads // plan.n,
            head_dim=cfg.resolved_head_dim,
        )
    if plan.mlp:
        kw.update(d_ff=cfg.d_ff // plan.n)
    return dataclasses.replace(cfg, **kw)


def _leaf_rule(name: str, plan: TPPlan) -> Optional[int]:
    """Slice axis for a param leaf path, or None (replicated)."""
    parts = name.split("/")
    if len(parts) < 2:
        return None
    sub, leaf = parts[-2], parts[-1]
    if sub in _ATTN_SUBLAYERS:
        return plan.rules.get(f"attn/{leaf}")
    if sub in _MLP_SUBLAYERS:
        return plan.rules.get(f"mlp/{leaf}")
    return None


def shard_params(params: Any, plan: TPPlan, index: int) -> Any:
    """Materialize shard ``index``'s param tree.

    Dense leaves slice directly; packed/int8 CIM operand dicts route through
    ``simulator.shard_operands`` (stored-byte slicing, exact).  Replicated
    leaves are returned as-is (shared, not copied).
    """
    if not 0 <= index < plan.n:
        raise ValueError(f"shard index {index} outside [0, {plan.n})")
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: simulator.is_cim_operands(x)
    )
    out = []
    for path, leaf in flat:
        ax = _leaf_rule(shrules._path_name(path), plan)
        if ax is None or plan.n == 1:
            out.append(leaf)
        elif simulator.is_cim_operands(leaf):
            out.append(simulator.shard_operands(leaf, axis=ax, index=index, n=plan.n))
        else:
            dim = leaf.shape[ax]
            if dim % plan.n:
                raise ValueError(
                    f"{shrules._path_name(path)}: axis {ax} extent {dim} not "
                    f"divisible by {plan.n} (plan_tp should have replicated this)"
                )
            lo = index * (dim // plan.n)
            sl = [slice(None)] * leaf.ndim
            sl[ax] = slice(lo, lo + dim // plan.n)
            out.append(leaf[tuple(sl)])
    return jax.tree_util.tree_unflatten(treedef, out)


def stack_shards(shards: Sequence[Any]) -> Any:
    """Stack per-shard pytrees on a new leading shard axis."""
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *shards)


def prepare_tp_params(params: Any, plan: TPPlan, prepare=None) -> Any:
    """Shard -> (optionally) prepare -> stack: the serving-ready TP tree.

    ``prepare`` defaults to ``steps.prepare_serving_params`` (the once-per-
    deployment packed->dense decompression on non-TPU backends).  Preparing
    AFTER slicing is exact: densify and stored-byte slicing commute.
    """
    if prepare is None:
        from repro.launch.steps import prepare_serving_params as prepare
    return stack_shards([prepare(shard_params(params, plan, i)) for i in range(plan.n)])


def place_shards(tree: Any, plan: TPPlan, devices) -> Any:
    """Lay a stacked per-shard tree over ``devices``: shard i on device i.

    The layout ``shard_map`` expects for its stacked inputs, so dispatches
    find params and pools already in place instead of moving them from the
    device the stacking ran on every call.
    """
    mesh = Mesh(np.asarray(devices), (plan.axis,))
    return jax.device_put(tree, NamedSharding(mesh, P(plan.axis)))


def tree_has_packed(params: Any) -> bool:
    """True if any leaf of ``params`` is a packed CIM operand dict."""
    found = False
    for leaf in jax.tree.leaves(
        params, is_leaf=lambda x: simulator.is_cim_operands(x)
    ):
        if simulator.is_cim_operands(leaf) and "planes_packed" in leaf:
            found = True
    return found


# ---------------------------------------------------------------------------
# SPMD execution of unmodified single-shard step functions
# ---------------------------------------------------------------------------

def _spmd(fn, plan: TPPlan, stacked_in: Sequence[bool], devices=None):
    """Run ``fn`` once per shard with ``plan.axis`` bound for its psums.

    ``stacked_in[i]`` marks positional arg ``i`` as carrying the leading
    shard axis (per-shard params / pools / caches); everything else is
    replicated (tokens, tables, keys).  Outputs all come back with the shard
    axis leading.

    ``devices=None`` -> ``jax.vmap`` with ``axis_name=plan.axis``: one
    device computes every shard, psum reduces over the vmap axis —
    numerically the SPMD program, bit-for-bit, which is what lets a
    single-CPU test pin multi-shard parity.  ``devices=[...]`` (len == n)
    -> ``shard_map`` over a 1-axis Mesh: shard i's slice lands on device i
    and psum lowers to a cross-device all-reduce.
    """
    if devices is None:
        in_axes = tuple(0 if s else None for s in stacked_in)
        return jax.vmap(fn, in_axes=in_axes, out_axes=0, axis_name=plan.axis)
    if len(devices) != plan.n:
        raise ValueError(f"need {plan.n} devices for {plan.n} shards, got {len(devices)}")
    mesh = Mesh(np.asarray(devices), (plan.axis,))
    in_specs = tuple(P(plan.axis) if s else P() for s in stacked_in)

    def body(*args):
        local = [
            jax.tree.map(lambda x: x[0], a) if s else a
            for a, s in zip(args, stacked_in)
        ]
        out = fn(*local)
        return jax.tree.map(lambda x: x[None], out)

    # check_vma=False: the body is the unmodified single-shard step, whose
    # scan carries and kernels carry no varying-axis annotations; its psums
    # are the only cross-shard communication, placed explicitly
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P(plan.axis), check_vma=False
    )


def tp_step(fn, plan: TPPlan, stacked_in: Sequence[bool], stacked_out: Sequence[bool], devices=None):
    """Engine-step adapter: same signature as the unsharded step.

    Tuple outputs marked False in ``stacked_out`` are reduced to shard 0
    INSIDE the wrapper (they are replicated across shards — tokens, PRNG
    keys), so the engine's host scheduler reads exactly the shapes it
    always has; True outputs (the per-shard KV pools) keep their leading
    shard axis and flow back into the next dispatch.
    """
    inner = _spmd(fn, plan, stacked_in, devices)

    def wrapped(*args):
        out = inner(*args)
        return tuple(
            o if keep else jax.tree.map(lambda x: x[0], o)
            for o, keep in zip(out, stacked_out)
        )

    return wrapped


# ---------------------------------------------------------------------------
# Sharded lockstep generation (the serve.generate twin)
# ---------------------------------------------------------------------------

def make_tp_generator(
    cfg: ArchConfig, params: Any, batch, *, n: int, gen_len: int,
    greedy: bool = True, seed: int = 0, plan: Optional[TPPlan] = None,
    devices=None,
):
    """Compile an ``n``-way tensor-parallel prefill+decode pipeline.

    Mirrors ``serve.make_generator`` (same PRNG schedule, same sampling
    path, scan decode loop) with every dispatch ``_spmd``-wrapped; returns
    ``timed_run() -> (tokens, seconds)``.  Token streams match the solo
    single-device generator: bit-identical at ``n == 1`` (psum over a
    1-shard axis is the identity), and token-identical at ``n > 1`` — the
    repo's serving parity contract (logits only reassociate the psum).
    """
    import time

    from repro.launch.steps import (
        cache_donation, make_decode_loop, make_prefill_step, serving_jit,
    )
    from repro.models import api

    if plan is None:
        plan = plan_tp(cfg, n, packed=tree_has_packed(params))
    elif plan.n != n:
        raise ValueError(f"plan is {plan.n}-way, asked for {n}")
    cfg_l = local_config(cfg, plan)
    tp_params = prepare_tp_params(params, plan)

    b, prompt_len = batch["tokens"].shape
    prefill = serving_jit(_spmd(make_prefill_step(cfg_l), plan, (True, False), devices))
    decode = serving_jit(
        _spmd(
            make_decode_loop(cfg_l, gen_len - 1, greedy=greedy),
            plan, (True, True, False, False, False), devices,
        ),
        donate_argnums=cache_donation(),
    )
    cache = jax.tree.map(
        lambda x: jnp.zeros((plan.n, *x.shape), x.dtype),
        api.init_cache(cfg_l, b, prompt_len + gen_len),
    )
    merge = serving_jit(
        _spmd(lambda c, pc: api.merge_prefill_cache(cfg_l, c, pc), plan, (True, True), devices)
    )
    key = jax.random.PRNGKey(seed)

    def pick(logits, key):
        if greedy:
            return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        return jax.random.categorical(sub, logits[:, -1])[:, None].astype(jnp.int32), key

    def run(key):
        logits, pf_cache = prefill(tp_params, batch)
        run_cache = merge(cache, pf_cache)
        # post-psum activations are replicated: every shard's logits are the
        # full unembedding, so shard 0 is THE logits (same for tokens below)
        tok, key = pick(logits[0], key)
        toks, _ = decode(tp_params, run_cache, tok, key, jnp.int32(prompt_len))
        tokens = jnp.concatenate([tok, toks[0]], axis=1)
        jax.block_until_ready(tokens)
        return tokens

    run(key)  # warmup: compile outside any timed region

    def timed_run():
        t0 = time.time()
        tokens = run(key)
        return tokens, time.time() - t0

    return timed_run


def tp_generate(
    cfg: ArchConfig, params: Any, batch, *, n: int, gen_len: int,
    greedy: bool = True, seed: int = 0, repeats: int = 1, plan: Optional[TPPlan] = None,
    devices=None,
):
    """Sharded twin of ``serve.generate``: returns (tokens, tok/s)."""
    timed_run = make_tp_generator(
        cfg, params, batch, n=n, gen_len=gen_len, greedy=greedy, seed=seed,
        plan=plan, devices=devices,
    )
    best = float("inf")
    for _ in range(max(1, repeats)):
        tokens, dt = timed_run()
        best = min(best, dt)
    return tokens, batch["tokens"].shape[0] * gen_len / best


# ---------------------------------------------------------------------------
# Per-shard crossbar pools + scrub coordination
# ---------------------------------------------------------------------------

def build_sharded_deployment(params: Any, spec, config, n: int, *, pools=None):
    """Deploy a model across ``n`` per-shard CrossbarPools.

    Pool *sections* live over SWS-sorted flat weights — a layout orthogonal
    to the serving (K, N) axes — so physical storage partitions by TENSOR,
    not by tensor-axis slice: eligible tensors round-robin across the shard
    pools in ``iter_weights`` order.  The per-tensor PRNG schedule is the
    global ``build_deployment`` schedule (one split per tensor in global
    iteration order), so under per-tensor pristine accounting
    (``pool.reset()`` between tensors, the planner's parity invariant (a))
    every tensor's plan — w_hat, stucking masks, transitions — is
    bit-identical to the unsharded deployment, and the summed wear of the
    shard pools equals the unsharded pool's exactly (the conservation law
    the TP battery pins).  With persistent pools the cross-tensor seams
    differ by construction — each tensor reprograms over a different
    predecessor than in the unsharded stream, exactly as two independent
    physical pools would — so only the PRNG schedule, not the achieved
    state, is partition-invariant there.

    Returns ``(plan, pools, owner)``: one merged DeploymentPlan covering
    every tensor (deploy_params-ready), the shard pools, and
    ``owner[name] -> shard`` for scrub/integrity routing.
    """
    from repro.core.planner import DeploymentPlan, analyze_tensor, iter_weights
    from repro.core.pool import CrossbarPool

    if n < 1:
        raise ValueError(f"shard count must be >= 1, got {n}")
    if pools is None:
        pools = [
            CrossbarPool(spec, config.crossbars, leveling=config.pool_leveling or "none")
            for _ in range(n)
        ]
    elif len(pools) != n:
        raise ValueError(f"need {n} pools, got {len(pools)}")
    key = jax.random.PRNGKey(config.seed)
    reports, deployed, owner = {}, {}, {}
    for i, (name, w) in enumerate(iter_weights(params, config)):
        key, sub = jax.random.split(key)
        report, w_hat = analyze_tensor(w, spec, config, sub, name=name, pool=pools[i % n])
        reports[name] = report
        deployed[name] = np.asarray(w_hat)  # host copy, as build_deployment keeps
        owner[name] = i % n
    plan = DeploymentPlan(spec=spec, config=config, reports=reports, deployed=deployed)
    return plan, pools, owner


class ShardedScrub:
    """Per-shard IntegrityManagers behind the ``Engine.attach_scrub`` duck
    type, with the round budget split round-robin so one mid-repair shard
    can never stall the replica: every ``scrub_round`` gives EVERY shard its
    budget slice (a shard deep in repairs spends its slice on repairs while
    the others keep scanning), and the merged report drives the engine's
    single repaired-plane refresh only once every shard is clean
    (``pending_faults`` sums across shards, and the engine refreshes at 0).
    """

    def __init__(self, managers: Sequence[Any]):
        if not managers:
            raise ValueError("ShardedScrub needs at least one IntegrityManager")
        self.managers = list(managers)
        self._next = 0  # rotate which shard scrubs first for budget fairness

    def pending_faults(self) -> int:
        return sum(m.pending_faults() for m in self.managers)

    def verify_all(self) -> bool:
        return all(m.verify_all() for m in self.managers)

    def scrub_round(self, budget_tiles: Optional[int] = None):
        n = len(self.managers)
        rep = None
        for j in range(n):
            m = self.managers[(self._next + j) % n]
            kw = {}
            if budget_tiles is not None:
                kw["budget_tiles"] = max(1, budget_tiles // n)
            r = m.scrub_round(**kw)
            if rep is None:
                rep = r
            else:
                # ScrubReport.merge treats ``pending`` as a level (last round
                # wins) — right for one manager over time, wrong across
                # DISTINCT pools, where the replica's pending work is the sum
                pend = rep.pending + r.pending
                rep.merge(r)
                rep.pending = pend
        self._next = (self._next + 1) % n
        return rep

    def rebuild_plan(self, plan):
        """Apply every shard's repaired reads onto one merged plan.

        Each manager only rebuilds tensors its own pool holds, so applying
        them in sequence touches disjoint ``deployed`` entries.
        """
        for m in self.managers:
            plan = m.rebuild_plan(plan)
        return plan
