"""Serving cells: an open-loop window through ``Engine.submit`` / ``Engine.step``.

The configuration's ``"reference"`` names the module that owns its
architecture (``bench/reference/<name>.py``: sizes, parameter leaves,
reference weights and gaps; ``bench/reference/__init__.py``); nothing here
knows one.  Set-up makes the configuration's weights on the device from
the seed, on the crossbar grid (``bench/weights.py``) of the reference's
leaves, turns the planner-deployed ones into packed serving operands with
``core.simulator.operands_from_dense`` (what
``deploy_params(materialize="packed")`` calls), builds the
``Engine`` and compiles and runs every dispatch shape the traffic can
reach (``Engine.prewarm``'s grid, cut to the mix's lengths).  The
window then offers the cell's traffic on its schedule: each request is
submitted when it is due and timed from then, whatever the engine is doing.
After the window closes the engine drains what was due in it.

Host times the harness takes itself, after each ``Engine.step`` returns
(the step reads its tokens back, so the dispatch has finished):

- a request's first token is the end of the step in which it first holds
  a token; its completion is the end of the step that retired it;
- ``ttft_p50_ms``: median over every request due in the window of first
  token - due (``ttft_p90_ms`` likewise, for the sweep); ``tpot_p90_ms``:
  90th percentile of (completion - first token) / (tokens - 1).  A request
  that never completes counts with the time it had waited when the run
  gave up;
- ``output_tok_per_s``: tokens emitted by steps that ended inside the
  window, over the window.  Below the knee it follows the offered load,
  not the system's speed: the sweep prints it, a cell above the knee
  would report it.

``correct`` compares what the window served with the float32 reference
the configuration names: a seeded sample of the finished requests, the
longest among them, re-run over prompt + served tokens; the number
compared is the widest gap by which a served token's reference logit lies
below the reference's best at its position.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, weights as W
from bench import trace as T

PLANNER_ENCODING = "sign_magnitude"
CONTROL = "float8_e4m3fn"  # the type the control rounds matmul inputs to (bfloat16 configs)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def _names(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): leaf
            for path, leaf in flat}


def _nest(flat: dict) -> dict:
    out: dict = {}
    for name, val in flat.items():
        node = out
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    segs = out["segments"]
    out["segments"] = [segs[str(i)] for i in range(len(segs))]
    return out


def reference(conf: dict):
    """The reference module the configuration names (``"reference"``)."""
    return common.load_module("reference", conf["reference"])


def served_params(arch, ref, model: dict, seed: int):
    """The served parameter tree, made on the device in one jitted call.

    The tree is the one ``ref.leaves(model)`` declares, and the program's
    own tree has to match it leaf for leaf.  Planner-deployed matrices
    become packed operands (norm gains, which the program serves dense,
    stay dense grid values); the rest are dense."""
    from repro.core import simulator
    from repro.core.planner import MATERIALIZE_DENSE_ONLY, PlannerConfig, iter_weights
    from repro.models import api

    lay = W.layout(ref.leaves(model))
    shapes = _names(jax.eval_shape(functools.partial(api.init, cfg=arch), jax.random.PRNGKey(0)))
    if {n: tuple(s.shape) for n, s in shapes.items()} != {l["name"]: l["shape"] for l in lay}:
        raise ValueError(f"{arch.name}: the program's parameter tree is not the layout "
                         f"{ref.__name__} declares")
    deployed = {n for n, _ in iter_weights(shapes, PlannerConfig())}
    if deployed != {l["name"] for l in lay if l["grid"]}:
        raise ValueError(f"{arch.name}: the planner deploys {sorted(deployed)}, "
                         f"not the tensors bench/weights.py puts on {ref.__name__}'s grid")

    def packed(leaf) -> bool:
        return leaf["grid"] and not set(leaf["name"].split("/")) & set(MATERIALIZE_DENSE_ONLY)

    def make(key):
        out = {}
        for leaf in lay:
            def one(i, leaf=leaf):
                w = W.leaf_slice(key, leaf, i)
                if packed(leaf):
                    return simulator.operands_from_dense(
                        w, leaf["step"], 0.0, PLANNER_ENCODING, W.COLS, materialize="packed")
                return w
            n = leaf["shape"][0]
            out[leaf["name"]] = jax.lax.map(one, jnp.arange(n)) if leaf["stacked"] else one(0)
        return out

    flat = jax.jit(make)(W.base_key(seed))
    jax.block_until_ready(flat)
    return _nest(flat)


def _dispatch_fns(eng) -> list:
    fns = list(getattr(eng, "_decode_loops", {}).values())
    fns += list(getattr(eng, "_fused_steps", {}).values())
    if getattr(eng, "_prefill_step", None) is not None:
        fns.append(eng._prefill_step)
    return fns


def compiled_count(eng) -> int:
    """Programs the engine's jitted dispatches hold (grows on each compile)."""
    return sum(f._cache_size() for f in _dispatch_fns(eng))


def dispatch_shapes(eng, mix: dict) -> list[tuple]:
    """The split dispatches this traffic can reach, from the engine's own
    row and page buckets: ``("decode", q, rows, pages)`` for every quantum,
    ``("prefill", rows, pages)``, with pages kept to what the mix's prompt
    and output lengths can address (a decode view covers the prompt plus a
    quantum at least; a prefill view covers whole chunks of the prompt)."""
    page, chunk = eng.ecfg.page_size, eng.ecfg.prefill_chunk
    p_min, p_max, o_max = mix["prompt"]["min"], mix["prompt"]["max"], mix["output"]["max"]
    pages, rows = eng._page_buckets(), eng._row_buckets()

    def bucket(tokens: int) -> int:
        n = -(-tokens // page)
        return min((b for b in pages if b >= n), default=pages[-1])

    out = []
    for q in eng._decode_loops:
        lo, hi = bucket(p_min + q), bucket(p_max + o_max + q)
        out += [("decode", q, r, p) for r in rows for p in pages if lo <= p <= hi]
    lo, hi = bucket(chunk), bucket(((p_max - 1) // chunk + 1) * chunk)
    out += [("prefill", r, p) for r in rows for p in pages if lo <= p <= hi]
    return out


def _dispatch(eng, shape):
    """(jitted dispatch, dummy arguments aimed at the dummy page, index of
    the pools in its outputs), as ``Engine.prewarm`` builds them."""
    if shape[0] == "decode":
        _, q, rows, pages = shape
        return eng._decode_loops[q], (
            eng.params, eng.pools, np.zeros((rows, pages), np.int32),
            np.zeros((rows, 3), np.int32), np.zeros((rows, 2), np.uint32)), 1
    _, rows, pages = shape
    meta = np.zeros((rows, 4), np.int32)
    meta[:, 1] = 1
    return eng._prefill_step, (
        eng.params, eng.pools, np.zeros((rows, pages), np.int32),
        np.zeros((rows, eng.ecfg.prefill_chunk), np.int32), meta,
        np.zeros((rows, 2), np.uint32)), 2


def warm(eng, shapes: list[tuple]) -> None:
    """Compile (or load from the persistent cache) and run every shape once,
    as ``Engine.prewarm`` does, so that none compiles in the window.  One at
    a time: lowering holds the interpreter lock, and lowering ahead of the
    call took half the time of a bare first call (TPU v5e)."""
    for shape in shapes:
        fn, args, at = _dispatch(eng, shape)
        fn.lower(*args).compile()
        eng.pools = fn(*args)[at]
    jax.block_until_ready(jax.tree.leaves(eng.pools))


def warm_requests(eng, mix: dict) -> None:
    """Serve the mix's shortest and longest prompt, each with its shortest
    answer, through ``Engine.run``, so that whatever a request's path runs
    besides the warmed dispatches (admission, first token, retirement) is
    built before the window; then forget them."""
    from repro.launch.engine import Request

    p, o = mix["prompt"], mix["output"]
    eng.run([Request(rid=-1 - i, prompt=np.zeros(n, np.int32), max_new_tokens=o["min"],
                     greedy=True, seed=0, arrival_time=0.0)
             for i, n in enumerate((p["min"], p["max"]))])
    eng.results.clear()


class ProgramCount:
    """Programs compiled or loaded from the persistent cache while ``on``:
    every backend compile JAX makes, eager operations' too (its
    ``backend_compile_duration`` event), and their seconds."""

    def __init__(self):
        self.on, self.n, self.s = False, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **_) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

def run_window(eng, reqs: list[dict], seconds: float, *, drain_s: float = 60.0,
               tracer: T.Tracer | None = None, trace_at: tuple[float, float] | None = None):
    """Offer ``reqs`` on their schedule for ``seconds``, then drain.

    Returns per-request records, the step spans, the tokens emitted inside
    the window, generator lateness, and the time the run gave up (if)."""
    from repro.launch.engine import Request

    n = len(reqs)
    first: dict[int, float] = {}
    done: dict[int, float] = {}
    steps: list[tuple[float, float]] = []
    late: list[float] = []
    emitted_in_window = 0
    retired_tokens = 0
    i = 0
    tracer = tracer or T.Tracer("")
    t0 = time.perf_counter()
    gave_up = None
    while True:
        now = time.perf_counter() - t0
        if trace_at is not None:
            if not tracer.on and trace_at[0] <= now < trace_at[1]:
                tracer.start()
            elif tracer.on and now >= trace_at[1]:
                tracer.stop()
                trace_at = None
        while i < n and reqs[i]["due"] <= now:
            r = reqs[i]
            eng.submit(Request(rid=r["rid"], prompt=r["prompt"], max_new_tokens=r["max_new"],
                               greedy=True, seed=r["rid"], arrival_time=r["due"]))
            late.append(now - r["due"])
            i += 1
        busy = bool(eng.waiting) or any(s is not None for s in eng.slots)
        if i >= n and not busy:
            break
        if now >= seconds + drain_s:
            gave_up = now
            break
        if not busy:
            with tracer.span("bench.wait_arrival"):
                time.sleep(max(0.0, min(reqs[i]["due"] - now, 0.002)))
            continue
        with tracer.span("bench.step"):
            ts = time.perf_counter() - t0
            did = eng.step(ts)
            te = time.perf_counter() - t0
        if did:
            steps.append((ts, te))
        live = 0
        for s in eng.slots:
            if s is not None:
                live += len(s.generated)
                if s.generated and s.req.rid not in first:
                    first[s.req.rid] = te
        for rid, res in eng.results.items():
            if rid not in done:
                done[rid] = te
                first.setdefault(rid, te)
                retired_tokens += len(res.tokens)
        if te <= seconds:
            emitted_in_window = live + retired_tokens
    if tracer.on:
        tracer.stop()
    end = time.perf_counter() - t0
    records = []
    for r in reqs:
        res = eng.results.get(r["rid"])
        ok = res is not None and res.status == "ok" and len(res.tokens) == r["max_new"]
        records.append({
            "rid": r["rid"], "due": r["due"], "prompt": r["prompt"], "max_new": r["max_new"],
            "admitted": res.t_admitted if res is not None else None,
            "first": first.get(r["rid"]), "done": done.get(r["rid"]) if ok else None,
            "tokens": list(res.tokens) if res is not None else [], "ok": ok,
        })
    return {"records": records, "steps": steps, "emitted": emitted_in_window, "late": late,
            "end": end, "gave_up": gave_up, "seconds": seconds}


def end_to_end(win: dict) -> dict:
    recs, end = win["records"], win["end"]
    ttft = [((r["first"] if r["first"] is not None else end) - r["due"]) for r in recs]
    tpot = []
    for r in recs:
        if r["ok"] and r["max_new"] > 1:
            tpot.append((r["done"] - r["first"]) / (r["max_new"] - 1))
        elif not r["ok"]:
            tpot.append(end - r["due"])
    return {
        "output_tok_per_s": win["emitted"] / win["seconds"],
        "ttft_p50_ms": 1e3 * common.percentile(ttft, 50),
        "ttft_p90_ms": 1e3 * common.percentile(ttft, 90),
        "tpot_p90_ms": 1e3 * common.percentile(tpot, 90) if tpot else 0.0,
    }


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def sample(records: list[dict], seed: int, min_tokens: int, max_requests: int) -> list[dict]:
    """A seeded sample of the finished requests with the longest in it."""
    ok = [r for r in records if r["ok"]]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (r["prompt"].size + len(r["tokens"]), r["rid"]))
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    rest = [ok[j] for j in rng.permutation(len(ok)) if ok[j] is not longest]
    out, tok = [longest], len(longest["tokens"])
    for r in rest:
        if tok >= min_tokens or len(out) >= max_requests:
            break
        out.append(r)
        tok += len(r["tokens"])
    return out


def check(ref, model: dict, seed: int, picked: list[dict], pad_to: int, *,
          control=None) -> float:
    wts = ref.Weights(model, seed)
    gaps = ref.served_gaps(wts, [(r["prompt"], r["tokens"]) for r in picked], pad_to,
                           control=control)
    return float(np.max(gaps)) if gaps.size else float("inf")


def judge(cell: dict, gap: float, unfinished: int) -> tuple[bool, dict]:
    """(correct, compared): the widest gap and the unfinished requests
    against their limits."""
    compared = {
        "max_gap": {"value": gap, "limit": cell["limits"]["max_gap"]},
        "unfinished": {"value": unfinished, "limit": 0},
    }
    return bool(gap <= cell["limits"]["max_gap"] and unfinished == 0), compared


def control(ctx: dict, rounding) -> tuple[bool, dict]:
    """The control, judged as a run is: the reference (``ctx["ref"]``) in
    the program's place with every matmul input rounded to ``rounding``,
    over the run's own sample (same prompts and served tokens); at each
    position the token the control puts first.  It serves every request it
    is given."""
    cell, seed = ctx["cell"], ctx["args"].seed
    picked = sample(ctx["window"]["records"], seed, cell["sample"]["min_tokens"],
                    cell["sample"]["max_requests"])
    gap = check(ctx["ref"], ctx["model"], seed, picked, ctx["config"]["engine"]["max_seq_len"],
                control=rounding)
    return judge(cell, gap, 0)


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

def setup(arch, ref, conf: dict, mix: dict, seed: int):
    from repro.launch.engine import Engine, EngineConfig

    model = ref.dims(conf)
    t = time.perf_counter()
    params = served_params(arch, ref, model, seed)
    common.log(f"setup: params made and packed in {time.perf_counter() - t:.1f} s")
    eng = Engine(arch, params, EngineConfig(**conf["engine"]))
    if eng.ecfg.fused:
        raise ValueError("serving cells warm the split dispatches: the fused dispatch's "
                         "shapes depend on timing and cannot all be warmed")
    del params
    t = time.perf_counter()
    shapes = dispatch_shapes(eng, mix)
    warm(eng, shapes)
    common.log(f"setup: {len(shapes)} dispatch shapes compiled (or loaded) and run in "
               f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    warm_requests(eng, mix)
    common.log(f"setup: shortest and longest prompt served in {time.perf_counter() - t:.2f} s")
    return model, eng


def run(ctx: dict) -> tuple[dict, dict, dict]:
    """One run of a serving cell -> (result without metrics, e2e, compared)."""
    cell, conf, mix, args = ctx["cell"], ctx["config"], ctx["traffic"], ctx["args"]
    arch, dev = ctx["arch"], ctx["device"]
    ctx["ref"] = ref = reference(conf)
    model, eng = setup(arch, ref, conf, mix, args.seed)
    rate = cell["rate_per_s"]
    reqs = common.load_module("traffic", mix["generator"]).generate(
        mix, rate=rate, seconds=args.seconds, seed=args.seed, vocab=model["vocab_size"])
    tracer, trace_at = None, None
    if args.trace:
        tracer = T.Tracer(ctx["trace_dir"])
        span = min(cell.get("trace_seconds", 6.0), args.seconds / 2)
        trace_at = (args.seconds / 3, args.seconds / 3 + span)
    ctx["trace_from"] = trace_at[0] if trace_at else None
    compiled0 = compiled_count(eng)
    built = ProgramCount()
    ctx["setup_s"] = time.perf_counter() - ctx["t_start"]
    built.on = True
    win = run_window(eng, reqs, args.seconds, drain_s=cell.get("drain_s", 60.0),
                     tracer=tracer, trace_at=trace_at)
    built.on = False
    compiles = compiled_count(eng) - compiled0
    recs = win["records"]
    n_beyond = len(recs) - int(np.ceil(0.9 * len(recs)))
    common.log(
        f"window: {len(recs)} requests due at {rate} /s over {args.seconds} s; "
        f"{sum(r['ok'] for r in recs)} finished, run ended {win['end']:.2f} s after the "
        f"window opened{' (gave up)' if win['gave_up'] else ''}; {len(win['steps'])} "
        f"dispatching steps; {n_beyond} requests beyond p90; compilations inside the window: "
        f"{compiles}; programs built or loaded inside it: {built.n} in {built.s:.3f} s; "
        f"longest step {max((e - s for s, e in win['steps']), default=0):.3f} s; "
        f"generator lateness max {1e3 * max(win['late'], default=0):.3f} ms, "
        f"mean {1e3 * float(np.mean(win['late'] or [0])):.3f} ms")
    e2e = end_to_end(win)
    e2e["setup_s"] = ctx["setup_s"]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    ctx["memory_peak_bytes"] = int(peak)
    ctx["window"] = win
    ctx["model"] = model
    ctx["compiles_in_window"] = compiles
    del eng
    gc.collect()

    picked = sample(recs, args.seed, cell["sample"]["min_tokens"], cell["sample"]["max_requests"])
    t = time.perf_counter()
    gap = check(ref, model, args.seed, picked, conf["engine"]["max_seq_len"])
    common.log(f"reference: {len(picked)} requests, {sum(len(r['tokens']) for r in picked)} "
               f"served tokens compared in {time.perf_counter() - t:.1f} s")
    unfinished = sum(not r["ok"] for r in recs)
    correct, compared = judge(cell, gap, unfinished)
    result = {"correct": correct, "attempted": len(recs), "failed": unfinished}
    return result, e2e, compared


def waiting(records: list[dict], t: float) -> int:
    """Requests due by ``t`` and not yet in a slot at ``t`` (the queue)."""
    return sum(r["due"] <= t and (r["admitted"] is None or r["admitted"] > t) for r in records)


def sweep(ctx: dict, rates: list[float]) -> None:
    """One set-up, then one window per rate: the knee is the highest rate
    whose queue does not grow through the window (printed per rate, no
    result line)."""
    cell, conf, mix, args = ctx["cell"], ctx["config"], ctx["traffic"], ctx["args"]
    model, eng = setup(ctx["arch"], reference(conf), conf, mix, args.seed)
    gen = common.load_module("traffic", mix["generator"])
    for k, rate in enumerate(rates):
        reqs = gen.generate(mix, rate=rate, seconds=args.seconds, seed=args.seed + k,
                            vocab=model["vocab_size"])
        c0 = compiled_count(eng)
        win = run_window(eng, reqs, args.seconds, drain_s=cell.get("drain_s", 60.0))
        e2e = end_to_end(win)
        recs, w = win["records"], args.seconds
        common.log(
            f"sweep rate {rate}: {len(recs)} requests, {sum(r['ok'] for r in recs)} finished, "
            f"queue at {w / 4:.0f}/{w / 2:.0f}/{3 * w / 4:.0f}/{w:.0f} s: "
            f"{waiting(recs, w / 4)}/{waiting(recs, w / 2)}/{waiting(recs, 3 * w / 4)}/"
            f"{waiting(recs, w)}; drained {win['end'] - w:.1f} s after close; "
            f"tok/s {e2e['output_tok_per_s']:.1f}, ttft p90 {e2e['ttft_p90_ms']:.0f} ms, "
            f"tpot p90 {e2e['tpot_p90_ms']:.1f} ms; compiles {compiled_count(eng) - c0}")
