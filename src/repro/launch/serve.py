"""Batched serving driver: prefill + decode with optional CIM-deployed weights.

Serves a model with batched requests through the same prefill/serve_step
functions the dry-run lowers, optionally swapping every eligible weight for
its crossbar-deployed (quantized + bit-stuck) counterpart so the *serving*
accuracy impact of the paper's technique is observable end to end.  With
``--cim`` the deployment streams through a persistent ``CrossbarPool``, so
the report includes physical wear: max/mean per-cell writes and the
endurance-budget exhaustion horizon (how many such deployments the pool
survives).

Serving representation (``--materialize``): ``dense`` serves the achieved
weights as ordinary f32 matmuls (the baseline); ``packed`` serves straight
from the crossbar state — bit-packed plane operands (the same canonical
packed words the planner/pool hold) flowing through the Pallas
``cim_matmul`` packed kernel on TPU (portable packed reference elsewhere);
``planes_int8`` is the one-byte-per-bit-cell traffic baseline.

Stored-plane codec (``--codec``, ``core/planes.py``): ``raw`` | ``const_rle``
| ``col_perm`` | ``col_perm_rle``.  Non-raw codecs change the physical bits
the pool programs (column-similarity reordering cuts reprogramming
transitions; constant-tile elision cuts weight traffic) and, with
``--materialize packed``, ride into the serving operands (plane-axis
reorder + zero-tile kernel skipping).  Token streams are bit-identical to
dense under every codec — the decode contract of ``core.planes``.

Decode loop (``--loop``): ``scan`` (default) runs the whole generation as a
single ``lax.scan`` dispatch with the KV cache donated, so decode never
copies the cache between tokens; ``python`` keeps the per-token dispatch
loop (cache still donated per step where the backend supports it).

Throughput accounting: one full prefill+decode step runs *before* the timer
starts, so jit compilation never pollutes the reported tok/s.

This driver serves ONE fixed-shape lockstep batch; ``launch.engine`` serves
streaming heterogeneous traffic (paged KV cache, fused prefill+decode,
preemption) with per-request token streams bit-identical to this module's
``generate`` — see docs/architecture.md for how the two relate.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --batch 4 --prompt-len 32 --gen 16 \
      [--cim --p-stuck 0.5 --pool-leveling lpt --materialize packed]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core.planner import (
    MATERIALIZATIONS,
    CrossbarSpec,
    PlannerConfig,
    build_deployment,
    deploy_params,
)
from repro.core.planes import CODECS
from repro.core.pool import DEFAULT_ENDURANCE, LEVELINGS, CrossbarPool
from repro.launch.steps import (
    cache_donation,
    make_decode_loop,
    make_prefill_step,
    make_serve_step,
    prepare_serving_params,
    serving_jit,
)
from repro.models import api


def make_generator(
    cfg, params, batch, *, gen_len: int, greedy: bool = True, seed: int = 0,
    loop: str = "scan",
):
    """Compile a full prefill+decode pipeline once; returns ``timed_run()``
    -> (tokens, seconds).

    The first call made here (untimed) is the jit warmup; each subsequent
    ``timed_run`` re-serves the same batch through the already-compiled
    dispatches.  Benchmarks comparing several deployments keep one generator
    per variant alive and interleave timed passes, so every variant samples
    the same background-load conditions (see serving_throughput).
    """
    if loop not in ("scan", "python"):
        raise ValueError(f"unknown decode loop {loop!r}")
    b, prompt_len = batch["tokens"].shape
    # once-per-deployment packed->dense decompression on non-TPU backends;
    # every dispatch below (warmup included) reuses the prepared tree
    params = prepare_serving_params(params)
    prefill = serving_jit(make_prefill_step(cfg))
    donate = cache_donation()
    if loop == "scan":
        decode = serving_jit(
            make_decode_loop(cfg, gen_len - 1, greedy=greedy), donate_argnums=donate
        )
    else:
        serve = serving_jit(make_serve_step(cfg), donate_argnums=donate)

    # cache sized for the full generation; encdec keeps a src-len cross cache
    cache = api.init_cache(
        cfg, b, prompt_len + gen_len,
        src_len=prompt_len if cfg.encdec else None,
    )

    key = jax.random.PRNGKey(seed)

    def pick(logits, key):
        """Next token from the last position — one sampling path for every
        decode step, the first post-prefill token included."""
        if greedy:
            return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        return jax.random.categorical(sub, logits[:, -1])[:, None].astype(jnp.int32), key

    def run(key):
        """One full prefill + decode; called once untimed, then per pass."""
        logits, pf_cache = prefill(params, batch)
        # prefill returns per-segment caches of the prompt; copy into the full cache
        run_cache = api.merge_prefill_cache(cfg, cache, pf_cache)
        tok, key = pick(logits, key)
        if loop == "scan":
            toks, _ = decode(params, run_cache, tok, key, jnp.int32(prompt_len))
            tokens = jnp.concatenate([tok, toks], axis=1)
        else:
            out = [tok]
            for i in range(gen_len - 1):
                logits, run_cache = serve(params, run_cache, tok, jnp.int32(prompt_len + i))
                tok, key = pick(logits, key)
                out.append(tok)
            tokens = jnp.concatenate(out, axis=1)
        jax.block_until_ready(tokens)
        return tokens

    run(key)  # warmup: compile prefill + decode outside any timed region

    def timed_run():
        t0 = time.time()
        tokens = run(key)
        return tokens, time.time() - t0

    return timed_run


def generate(
    cfg, params, batch, *, gen_len: int, greedy: bool = True, seed: int = 0,
    loop: str = "scan", repeats: int = 1,
):
    """Prefill then decode ``gen_len`` tokens; returns (tokens, tok/s).

    The first prefill+decode step is executed once untimed (jit warmup):
    compile time used to land inside the timer and understate tok/s by an
    order of magnitude on short generations.  ``loop="scan"`` (default)
    fuses the decode loop into one donated-cache ``lax.scan`` dispatch;
    ``loop="python"`` is the legacy per-token dispatch loop.  Both share one
    sampling path and PRNG schedule, so tokens agree between loops.

    ``repeats``: the timed region for a reduced model is tens of
    milliseconds — a single sample swings tens of percent with scheduler /
    allocator noise, which is enough to invert the ordering of identical
    compute graphs (fp vs cim-dense are the same f32 matmuls).  Benchmarks
    pass ``repeats>=3`` and take the best run; tokens come from the last.
    """
    b, gen = batch["tokens"].shape[0], gen_len
    timed_run = make_generator(
        cfg, params, batch, gen_len=gen_len, greedy=greedy, seed=seed, loop=loop
    )
    best = float("inf")
    for _ in range(max(1, repeats)):
        tokens, dt = timed_run()
        best = min(best, dt)
    return tokens, b * gen / best


def main() -> None:
    """CLI entry: serve a (reduced) arch with fp weights, then optionally
    re-serve it crossbar-deployed (``--cim``) and report tok/s, token
    agreement, reprogramming speedups, pool wear, and the endurance
    horizon.  For streaming heterogeneous traffic use ``launch.engine``
    (continuous batching) instead; this driver serves one lockstep batch."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cim", action="store_true", help="serve crossbar-deployed weights")
    ap.add_argument(
        "--materialize", choices=MATERIALIZATIONS, default="dense",
        help="serving representation of deployed tensors (packed = bit-plane-native)",
    )
    ap.add_argument(
        "--codec", choices=CODECS, default="raw",
        help="stored-plane codec (core/planes.py): changes the physical bits "
             "the pool programs (and the priced transitions) and, with "
             "--materialize packed, the serving operand layout; token streams "
             "stay bit-identical to dense for every codec",
    )
    ap.add_argument(
        "--loop", choices=["scan", "python"], default="scan",
        help="decode loop: one fused lax.scan dispatch or per-token dispatches",
    )
    ap.add_argument("--p-stuck", type=float, default=0.5)
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--cols", type=int, default=10)
    ap.add_argument(
        "--min-size", type=int, default=PlannerConfig().min_size,
        help="smallest tensor (elements) deployed to crossbars",
    )
    ap.add_argument(
        "--pool-leveling", choices=LEVELINGS, default="none",
        help="wear-leveling chain->crossbar assignment for the pool",
    )
    ap.add_argument(
        "--endurance", type=float, default=DEFAULT_ENDURANCE,
        help="per-cell write endurance budget for the exhaustion horizon",
    )
    ap.add_argument(
        "--fault-rate", type=float, default=0.0,
        help="per-cell stuck-at rate (split evenly stuck-at-0/1) injected "
             "into the pool before deployment; reads go through the masks",
    )
    ap.add_argument(
        "--fault-hotspot", type=float, default=0.0,
        help="fraction of crossbars with 8x the stuck-at rate (the "
             "heterogeneous-yield setting 'fault' leveling remaps around)",
    )
    ap.add_argument(
        "--scrub", action="store_true",
        help="enable the online integrity layer (core/integrity.py): tile "
             "checksums + spare columns registered at program() time, with a "
             "scrub/repair summary in the report",
    )
    ap.add_argument(
        "--scrub-tiles", type=int, default=64,
        help="tile-verification budget per scrub round (bounds scrub latency)",
    )
    ap.add_argument(
        "--spare-cols", type=int, default=2,
        help="clean spare column planes per section (remap targets for hard "
             "stuck-at faults found by the scrubber)",
    )
    ap.add_argument(
        "--scrub-storm", type=float, default=0.0,
        help="after deployment, corrupt stored bits at this rate (plus 1/10th "
             "of it as new hard stuck cells), scrub to convergence, and report "
             "repair cost vs a full reprogram of the affected tensors",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if (args.scrub or args.scrub_storm > 0.0) and not args.cim:
        ap.error("--scrub/--scrub-storm apply to crossbar-deployed weights; add --cim")
    if args.scrub_storm > 0.0 and not args.scrub:
        ap.error("--scrub-storm needs the integrity layer; add --scrub")
    if args.codec != "raw":
        if not args.cim:
            ap.error("--codec applies to crossbar-deployed weights; add --cim")
        if args.materialize == "planes_int8":
            ap.error(
                "--codec encodes packed serving operands; --materialize "
                "planes_int8 has no stored-plane layout (use packed or dense)"
            )

    cfg = get_arch(args.arch, reduced=args.reduced)
    key = jax.random.PRNGKey(args.seed)
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, args.batch, args.prompt_len)

    tokens, tps = generate(cfg, params, batch, gen_len=args.gen, seed=args.seed, loop=args.loop)
    print(f"fp weights:   {tps:8.1f} tok/s   first request: {tokens[0, :12].tolist()}")

    if args.cim:
        spec = CrossbarSpec(rows=args.rows, cols=args.cols)
        planner_cfg = PlannerConfig(
            p_stuck=args.p_stuck,
            min_size=args.min_size,
            pool_leveling=args.pool_leveling,
            codec=args.codec,
        )
        pool = CrossbarPool(spec, planner_cfg.crossbars, leveling=args.pool_leveling)
        if args.scrub:
            from repro.core.integrity import IntegrityConfig

            pool.enable_integrity(IntegrityConfig(
                spare_cols=args.spare_cols, scrub_tiles=args.scrub_tiles,
            ))
        if args.fault_rate > 0.0:
            from repro.core import nonideal

            fstate = pool.inject_faults(
                nonideal.FaultModel(
                    stuck0=args.fault_rate / 2, stuck1=args.fault_rate / 2,
                    hotspot_fraction=args.fault_hotspot, hotspot_mult=8.0,
                ),
                jax.random.PRNGKey(args.seed),
            )
            cells = fstate.fault_cells()
            print(f"injected faults: {int(cells.sum())} stuck cells across "
                  f"{pool.n_crossbars} crossbars (worst {int(cells.max())}; "
                  f"{int(fstate.hot.sum())} hotspots)")
        plan = build_deployment(params, spec, planner_cfg, pool=pool)
        # dense materialization has no stored-plane layout to encode; the
        # plan's codec already shaped the pool's physical programming above
        codec = args.codec if args.materialize == "packed" else "raw"
        params_hat = deploy_params(params, plan, materialize=args.materialize, codec=codec)
        tokens_hat, tps_hat = generate(
            cfg, params_hat, batch, gen_len=args.gen, seed=args.seed, loop=args.loop
        )
        agree = float(jnp.mean((tokens == tokens_hat).astype(jnp.float32)))
        t = plan.totals()
        stats = pool.stats()
        horizon = stats.exhaustion_horizon(args.endurance)
        print(f"cim weights:  {tps_hat:8.1f} tok/s   ({args.materialize} materialization)"
              f"   first request: {tokens_hat[0, :12].tolist()}")
        print(f"token agreement: {agree:.3f}   reprog speedup: {t['total_speedup']:.2f}x "
              f"(sws {t['sws_speedup']:.2f}x)")
        print(f"pool wear: max cell {stats.max_cell_writes} writes, "
              f"mean {stats.mean_cell_writes:.2f}, total {stats.total_writes} "
              f"over {stats.tensors_seen} tensors")
        print(f"endurance horizon: ~{horizon:.3g} such deployments "
              f"@ {args.endurance:.0e} writes/cell ({args.pool_leveling} leveling)")
        if args.scrub:
            mgr = pool.integrity
            s = mgr.summary()
            print(f"integrity: {s['tensors']} tensors registered, {s['tiles']} "
                  f"checksum tiles, {s['spare_cols']} spare cols/section"
                  + (" + parity" if s["parity_col"] else ""))
            if args.scrub_storm > 0.0:
                st = mgr.storm(
                    jax.random.PRNGKey(args.seed + 1),
                    corrupt_rate=args.scrub_storm,
                    stuck_rate=args.scrub_storm / 10,
                )
                rep = mgr.scrub_until_clean()
                full = mgr.transitions_full_affected()
                ratio = rep.repair_transitions / max(full, 1)
                print(f"storm: {st['corrupted_bits']} bits corrupted, "
                      f"{st['new_stuck_cells']} new stuck cells -> "
                      f"{rep.detections} detections, {rep.rewrites} rewrites, "
                      f"{rep.remaps} remaps, {rep.migrations} migrations, "
                      f"{rep.tolerated} tolerated")
                print(f"repair cost: {rep.repair_transitions} transitions vs "
                      f"{full} full reprogram ({ratio:.4f}x); reads restored: "
                      f"{mgr.verify_all()}")


if __name__ == "__main__":
    main()
