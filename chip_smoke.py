#!/usr/bin/env python3
"""Chip smoke test: plan, program and serve internlm2-1.8b on a TPU.

Drives the system's main path once, in one process, through the entry
points a user calls, at the published widths of internlm2-1.8b (24 layers,
d_model 2048, 16 query / 8 KV heads, d_ff 8192, vocab 92544), with random
weights from ``--seed``:

1. ``core.planner.build_deployment`` streams every eligible tensor through
   a ``CrossbarPool`` at the default ``CrossbarSpec`` / ``PlannerConfig``,
   pricing with the compiled Pallas ``price_pairs`` kernel and sorting on
   the device;
2. ``deploy_params(materialize="packed")`` turns the achieved weights into
   packed bit-plane serving operands;
3. ``launch.engine.Engine`` (paged KV, fused dispatch) serves 4 requests.

It exits non-zero, with no result line, if any phase raises or any check
fails:

(a) every engine stream equals a solo ``launch.serve.generate`` of the same
    request, token for token (both run the packed kernels, so this is a
    scheduling-only contract);
(b) the packed deployment's logits over whole prompts stay within
    ``LOGIT_TOL_X_BF16`` times the error of a bfloat16 evaluation of the
    float32 reference (``models/reference.py``, same achieved weights);
(c) the compiled decode dispatch holds a packed ``cim_matmul`` custom call
    (``tpu_custom_call``) for every distinct packed linear it traces.

``--chips 4`` runs instead only the tensor-parallel path, ``Engine(tp=4)``
over four TPU devices, and what it is compared with, the one-chip engine on
the same deployment: shard placement, teacher-forced logits within
``TP_LOGIT_TOL_X_BF16`` times the one chip's own bfloat16 rounding, and
greedy-token agreement.

The last line of stdout is ``{"ok": true, "device": {...}}``.

Usage: python chip_smoke.py [--chips 4] [--seed N]
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "internlm2-1.8b"
PROMPT_LENS = (256, 128, 256, 128)
GEN = 32
SAMPLED = {3}  # request ids that sample (the rest decode greedily)
TF_LEN = 128  # teacher-forced tokens per request for the logit checks
# (b): packed serving may sit at most this many times further from the
# float32 reference than bfloat16 arithmetic of the same math does.  The
# configuration computes in bfloat16; float8 (3 mantissa bits, 16x the
# unit roundoff) or int8 weights (~9x bfloat16's weight error) would fail.
LOGIT_TOL_X_BF16 = 3.0
# --chips 4: TP=4 may move the one-chip logits at most this many times as
# far as bfloat16 rounding moves them (one chip at bfloat16 against one
# chip at float32 activations).  The shards round their row-parallel
# partial sums to bfloat16 before the psum: one extra rounding per
# row-parallel output, beside the ~10 a layer already has.  A wrong slice
# or a missing or doubled psum moves the logits by their own size.
TP_LOGIT_TOL_X_BF16 = 2.0


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Phases shared by both modes
# ---------------------------------------------------------------------------

def make_params(cfg, seed: int):
    """Full-width random weights, built on the device and kept on the host
    (a checkpoint's place before deployment); returns the host tree."""
    import jax

    from repro.models import api

    t0 = time.perf_counter()
    params = jax.jit(api.init, static_argnums=1)(jax.random.PRNGKey(seed), cfg)
    host = jax.device_get(params)
    del params
    n = api.param_count(host)
    log(f"params: {n} weights ({n * 4 / 2**30:.2f} GiB f32) in {time.perf_counter() - t0:.1f} s")
    return host


def plan_and_program(host_params, pcfg):
    """build_deployment through a CrossbarPool, on the device paths only."""
    from repro.core import sws
    from repro.core.planner import CrossbarSpec, build_deployment
    from repro.core.pool import CrossbarPool
    from repro.kernels._util import on_tpu

    check(on_tpu(), "price_pairs takes the compiled Pallas kernel (backend is tpu)")
    check(not sws._use_host_sort(), "stable_argsort sorts on the device")
    spec = CrossbarSpec()
    pool = CrossbarPool(spec, pcfg.crossbars)
    t0 = time.perf_counter()

    def progress(name: str) -> None:
        log(f"  {time.perf_counter() - t0:7.1f} s: planning {name}")

    plan = build_deployment(host_params, spec, pcfg, pool=pool, progress=progress)
    dt = time.perf_counter() - t0
    tot = plan.totals()
    st = plan.pool_stats
    log(f"plan+program: {len(plan.reports)} tensors in {dt:.1f} s (host clock)")
    log(
        f"transitions: baseline {tot['transitions_baseline']} sws {tot['transitions_sws']} "
        f"final {tot['transitions_final']} (sws speedup {tot['sws_speedup']:.3f}x)"
    )
    log(
        f"cell writes: total {st['total_writes']} max/cell {st['max_cell_writes']} "
        f"programs {st['programs']}"
    )
    check(st["total_writes"] == tot["transitions_final"] > 0,
          "pool cell writes equal the plan's programmed transitions")
    # with full reprogramming every achieved weight is its source weight
    # rounded to the nearest of 2**cols - 1 levels: half a step at most
    check(all(r.quant_mse <= (r.scale / 2) ** 2 for r in plan.reports.values()),
          "achieved weights sit within half a quantization step of the source")
    return plan


def check_pricing_kernel(seed: int):
    """The compiled kernel against the jnp oracle on random sections."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.hamming import ops, ref

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.randint(k1, (4099, 16, 10), 0, 256, jnp.int32).astype(jnp.uint8)
    b = jax.random.randint(k2, (4099, 16, 10), 0, 256, jnp.int32).astype(jnp.uint8)
    got = np.asarray(ops.hamming_pairs(a, b, interpret=False))
    check(np.array_equal(got, np.asarray(ref.hamming_pairs(a, b))),
          "compiled hamming kernel is bit-exact with the jnp oracle")


def deploy(host_params, plan):
    import jax

    from repro.core.planner import deploy_params

    t0 = time.perf_counter()
    served = jax.device_put(deploy_params(host_params, plan, materialize="packed"))
    jax.block_until_ready(served)
    log(f"deploy_params(packed): {time.perf_counter() - t0:.1f} s")
    return served


def make_requests(cfg, seed: int):
    import numpy as np

    from repro.launch.engine import Request

    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=GEN, greedy=i not in SAMPLED, seed=seed + i)
        for i, n in enumerate(PROMPT_LENS)
    ]


def engine_config():
    from repro.launch.engine import EngineConfig

    return EngineConfig(
        max_slots=len(PROMPT_LENS), page_size=16,
        max_seq_len=max(PROMPT_LENS) + GEN, prefill_chunk=max(PROMPT_LENS),
        decode_quantum=8,
    )


def serve(eng, reqs, label: str):
    """A warm pass (compiles what the trace needs), then a timed pass."""
    import dataclasses

    t0 = time.perf_counter()
    first = eng.run(reqs)
    t_first = time.perf_counter() - t0
    again = [dataclasses.replace(r, rid=r.rid + 100) for r in reqs]
    t0 = time.perf_counter()
    second = eng.run(again)
    t_second = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in second)
    log(
        f"{label}: first pass {t_first:.1f} s ({len(eng._shapes_seen)} dispatch shapes, "
        f"compile included); second pass {t_second:.2f} s, {toks / t_second:.1f} tok/s "
        f"(host clock, {len(reqs)} requests x {GEN} tokens)"
    )
    check(all(a.tokens == b.tokens for a, b in zip(first, second)),
          f"{label}: the timed pass repeats the first pass's streams")
    check(all(r.status == "ok" and len(r.tokens) == GEN for r in first),
          f"{label}: every request completed")
    return first


def tf_batch(reqs):
    import numpy as np

    return {"tokens": np.stack([r.prompt[:TF_LEN] for r in reqs])}


def rel_err(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def agreement(a, b) -> float:
    import numpy as np

    return float(np.mean(np.argmax(np.asarray(a), -1) == np.argmax(np.asarray(b), -1)))


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def packed_linear_shapes(served) -> collections.Counter:
    """(cols, K/8, N) of every distinct packed linear; a layer-stacked
    operand traces once inside the layer scan, so it counts once."""
    from repro.core import simulator

    found = collections.Counter()

    def walk(t):
        if simulator.is_cim_operands(t):
            found[tuple(t["planes_packed"].shape[-3:])] += 1
        elif isinstance(t, dict):
            for v in t.values():
                walk(v)
        elif isinstance(t, (list, tuple)):
            for v in t:
                walk(v)

    walk(served)
    return found


def hlo_packed_calls(hlo: str) -> collections.Counter:
    """Plane-operand shapes (cols, K/8, N) of the packed ``cim_matmul``
    kernels in a compiled HLO module: its ``tpu_custom_call`` ops named
    after the Pallas kernel, read from their operand layout constraints."""
    found = collections.Counter()
    for line in hlo.splitlines():
        name = line.strip().removeprefix("ROOT ").split(" = ", 1)[0]
        if ('custom_call_target="tpu_custom_call"' not in line
                or not name.startswith("%cim_matmul_packed")):
            continue
        operands = line.split("operand_layout_constraints=", 1)[1].split(" metadata=", 1)[0]
        for m in re.finditer(r"u8\[(\d+),(\d+),(\d+)\]", operands):
            found[tuple(int(x) for x in m.groups())] += 1
    return found


def check_decode_hlo(eng, served) -> None:
    import numpy as np

    shapes = sorted(s for s in eng._shapes_seen if s[0] == "decode")
    check(bool(shapes), "the trace ran the decode dispatch")
    _, q, rows, pages = shapes[-1]
    compiled = eng._decode_loops[q].lower(
        eng.params, eng.pools, np.zeros((rows, pages), np.int32),
        np.zeros((rows, 3), np.int32), np.zeros((rows, 2), np.uint32),
    ).compile()
    want = packed_linear_shapes(served)
    got = hlo_packed_calls(compiled.as_text())
    log(f"decode dispatch (q={q}, rows={rows}, pages={pages}): packed linears {dict(want)}, "
        f"packed custom calls {dict(got)}")
    check(bool(want) and all(got[k] >= n for k, n in want.items()),
          "compiled decode dispatch runs every packed linear through cim_matmul")


def check_logits(cfg, host_params, plan, served, reqs) -> None:
    import jax
    import numpy as np

    from repro.launch.steps import serving_jit
    from repro.models import api, reference

    batch = tf_batch(reqs)
    packed = serving_jit(lambda p, b: api.forward(p, cfg, b)[0])(served, batch)
    packed = np.asarray(packed)
    ref_tree = jax.tree_util.tree_map_with_path(
        lambda path, leaf: plan.deployed.get(
            "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path), leaf
        ),
        host_params,
    )
    t0 = time.perf_counter()
    ref32 = np.asarray(reference.logits(ref_tree, cfg, batch["tokens"]))
    ref16 = np.asarray(reference.logits(ref_tree, cfg, batch["tokens"], dtype=jax.numpy.bfloat16))
    log(f"reference logits (f32 and bf16): {time.perf_counter() - t0:.1f} s")
    check(bool(np.isfinite(packed).all() and np.isfinite(ref32).all()), "logits are finite")
    err, floor = rel_err(packed, ref32), rel_err(ref16, ref32)
    log(
        f"logits {packed.shape}: packed vs f32 reference rel RMS {err:.3e}; bf16 reference "
        f"vs f32 {floor:.3e} (ratio {err / floor:.2f}); greedy agreement packed "
        f"{agreement(packed, ref32):.4f}, bf16 {agreement(ref16, ref32):.4f}"
    )
    check(err <= LOGIT_TOL_X_BF16 * floor,
          f"packed logits within {LOGIT_TOL_X_BF16}x the bf16 error of the f32 reference")


def run_one_chip(cfg, dev, seed: int) -> None:
    import numpy as np

    from repro.launch.engine import Engine
    from repro.launch.serve import generate

    from repro.core.planner import PlannerConfig

    host = make_params(cfg, seed)
    check_pricing_kernel(seed)
    plan = plan_and_program(host, PlannerConfig())
    served = deploy(host, plan)
    reqs = make_requests(cfg, seed)

    eng = Engine(cfg, served, engine_config())
    results = serve(eng, reqs, "engine")
    t0 = time.perf_counter()
    for req, res in zip(reqs, results):
        solo, _ = generate(cfg, served, {"tokens": req.prompt[None]}, gen_len=GEN,
                           greedy=req.greedy, seed=req.seed)
        check([int(t) for t in np.asarray(solo[0])] == res.tokens,
              f"request {req.rid} (prompt {req.prompt.size}, "
              f"{'greedy' if req.greedy else 'sampled'}): engine stream == solo generate")
    log(f"solo generate x{len(reqs)}: {time.perf_counter() - t0:.1f} s (compile included)")
    check_decode_hlo(eng, served)
    del eng
    check_logits(cfg, host, plan, served, reqs)
    stats = dev.memory_stats() or {}
    log(f"peak device memory: {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
        f"of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


# ---------------------------------------------------------------------------
# Four chips: TP=4 against the one-chip engine
# ---------------------------------------------------------------------------

def check_placement(tree, devs, what: str) -> None:
    import jax

    leaves = jax.tree.leaves(tree)
    ok = bool(leaves)
    for leaf in leaves:
        for sh in leaf.addressable_shards:
            i = sh.index[0].start
            ok &= sh.data.shape[0] == 1 and sh.device == devs[i]
        ok &= len({sh.device for sh in leaf.addressable_shards}) == len(devs)
    check(ok, f"{what}: shard i lives on device i")


def run_four_chips(cfg, devs, seed: int) -> None:
    import dataclasses

    import jax

    from repro.core import simulator
    from repro.launch.engine import Engine
    from repro.launch.steps import serving_jit
    from repro.models import api
    from repro.parallel import tp as tp_mod

    from repro.core.planner import PlannerConfig

    host = make_params(cfg, seed)
    plan = plan_and_program(host, PlannerConfig())
    served = deploy(host, plan)
    reqs = make_requests(cfg, seed)

    ecfg = engine_config()
    sharded = Engine(cfg, served, ecfg, tp=len(devs), tp_devices=devs)
    plan_tp = sharded._tp
    check(sharded._tp_devices == tuple(devs) and plan_tp.n == len(devs)
          and plan_tp.attn and plan_tp.mlp,
          f"Engine(tp={len(devs)}) shards attention and MLP over shard_map on "
          f"{len(devs)} distinct devices")
    stacked_planes = [
        leaf["planes_packed"] for leaf in jax.tree.leaves(
            sharded.params, is_leaf=simulator.is_cim_operands)
        if simulator.is_cim_operands(leaf)
    ]
    check_placement(stacked_planes, devs, "packed operands")
    check_placement(sharded.params, devs, "every stacked serving param")
    check_placement(sharded.pools, devs, "paged KV pool")

    one = Engine(cfg, served, ecfg)
    res_tp = serve(sharded, reqs, f"engine tp={len(devs)}")
    res_one = serve(one, reqs, "engine 1 chip")
    same = sum(a.tokens == b.tokens for a, b in zip(res_tp, res_one))
    tok_agree = sum(
        x == y for a, b in zip(res_tp, res_one) for x, y in zip(a.tokens, b.tokens)
    ) / sum(len(a.tokens) for a in res_one)
    log(f"streams identical tp vs 1 chip: {same}/{len(reqs)}; token agreement {tok_agree:.4f}")

    batch = tf_batch(reqs)
    fwd_tp = serving_jit(tp_mod.tp_step(
        lambda p, b: (api.forward(p, sharded.cfg_local, b)[0],),
        plan_tp, (True, False), (False,), sharded._tp_devices,
    ))
    logits_tp = fwd_tp(sharded.params, batch)[0]
    logits_one = serving_jit(lambda p, b: api.forward(p, cfg, b)[0])(one.params, batch)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        logits_32 = serving_jit(lambda p, b: api.forward(p, cfg32, b)[0])(one.params, batch)
    err, floor = rel_err(logits_tp, logits_one), rel_err(logits_one, logits_32)
    log(f"teacher-forced logits {logits_one.shape}: tp vs 1 chip rel RMS {err:.3e}; 1 chip "
        f"bf16 vs f32 activations {floor:.3e} (ratio {err / floor:.2f}); greedy agreement "
        f"{agreement(logits_tp, logits_one):.4f}")
    check(err <= TP_LOGIT_TOL_X_BF16 * floor,
          f"TP={len(devs)} logits within {TP_LOGIT_TOL_X_BF16}x the one chip's bf16 rounding")
    for i, d in enumerate(devs):
        st = d.memory_stats() or {}
        log(f"peak device memory dev{i}: {st.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import chip

    cache = chip.setup_compile_cache()
    import jax

    from repro.configs import get_arch

    try:
        devs = chip.require_tpu(args.chips)
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} count={len(jax.devices())} "
        f"using={len(devs)} compile_cache={cache}")
    cfg = get_arch(ARCH)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            run_one_chip(cfg, d0, args.seed)
        else:
            run_four_chips(cfg, devs, args.seed)
    except CheckFailed:
        return 1
    log(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
