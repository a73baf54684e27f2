"""Step functions: train_step / prefill_step / serve_step per architecture.

These are the functions the dry-run lowers and the runtime executes.  All
are pure (params, state, batch) -> (new state, metrics) functions suitable
for ``jax.jit`` with explicit in/out shardings.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import api
from repro.optim import AdamWConfig, adamw_update


def loss_fn(params, cfg: ArchConfig, batch: dict, remat: str = "none") -> tuple[jax.Array, dict]:
    logits, aux = api.forward(params, cfg, batch, remat=remat)
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
    if cfg.stub_prefix_len:
        # modality-stub positions carry no next-token target
        pos = jnp.arange(nll.shape[1])
        mask = (pos >= cfg.stub_prefix_len).astype(jnp.float32)[None]
        nll = nll * mask
        loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask) * nll.shape[0], 1.0)
    else:
        loss = jnp.mean(nll)
    return loss + aux, {"nll": loss, "aux": aux}


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig, *, remat: str = "full"):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    if remat not in ("none", "full", "dots"):
        raise ValueError(f"unknown remat policy {remat!r}")

    def train_step(params, opt_state, batch):
        f = functools.partial(loss_fn, cfg=cfg, batch=batch, remat=remat)
        (loss, parts), grads = jax.value_and_grad(f, has_aux=True)(params)
        new_params, new_state, opt_metrics = adamw_update(grads, opt_state, params, opt_cfg)
        metrics = {"loss": loss, **parts, **opt_metrics}
        return new_params, new_state, metrics

    return train_step


def _serving_params(params):
    """Backend policy for bit-packed weight operands in serving steps.

    On TPU, packed operand dicts flow through to the model unchanged — every
    decode step computes on them via the packed Pallas ``cim_matmul`` kernel,
    reading ~1 bit of weight HBM per bit cell.  On backends without the
    compiled kernel the packed representation is a *storage* format: the
    serve/prefill steps decompress it to dense achieved weights once per
    dispatch (inside jit, hoisted above the whole scan-over-tokens decode
    loop) instead of paying a per-token, per-site bit-unpack emulation.
    Int8-plane operands are exempt: they exist as the faithful per-step
    bit-sliced simulation baseline.

    Codec-encoded packed dicts (``core.planes.encode_operands``: plane-axis
    reorder + zero-tile flags) need no special casing here — ``densify`` and
    ``cim_linear`` both decode them exactly, so either route serves the same
    bits as raw operands.
    """
    from repro.core import simulator
    from repro.kernels._util import on_tpu

    return params if on_tpu() else simulator.densify_packed(params)


def prepare_serving_params(params):
    """Once-per-deployment host-side materialization of serving params.

    Same backend policy as ``_serving_params``, but executed *eagerly before
    any dispatch is built*: on non-TPU backends every packed operand dict is
    decompressed to dense achieved weights exactly once, and the resulting
    pytree is reused by every jitted variant (warmup + timed runs, every
    engine bucket).  Without this hoist the densify ops are traced into each
    dispatch and re-executed on device per call.  ``_serving_params`` stays
    inside the step functions as the TPU packed-flow policy (it is a cheap
    trace-time no-op on an already-prepared tree), so step makers remain
    correct for callers that skip preparation.
    """
    return _serving_params(params)


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, batch):
        return api.prefill(_serving_params(params), cfg, batch)

    return prefill_step


def make_serve_step(cfg: ArchConfig):
    """One-token decode: (params, cache, token, pos) -> (logits, cache)."""

    def serve_step(params, cache, token, pos):
        return api.decode_step(_serving_params(params), cfg, cache, token, pos)

    return serve_step


def serving_jit(fn, **kwargs):
    """``jax.jit`` for serving dispatches: bfloat16 is rounded wherever the
    program says so.

    By default XLA may keep a bfloat16 intermediate in float32 inside a
    fusion ("excess precision"), and which intermediates it keeps depends
    on fusion and tiling decisions that change with the dispatch shape.  On
    TPU that made one prompt's logits depend on its batch and made engine
    streams diverge from solo ``serve.generate`` of the same request
    (different dispatch shapes); with every rounding fixed by the program,
    results no longer depend on the shape of the batch around a row.
    """
    return jax.jit(fn, compiler_options={"xla_allow_excess_precision": False}, **kwargs)


def cache_donation() -> tuple[int, ...]:
    """``donate_argnums`` for the cache operand of serve_step / decode_loop.

    Donating the KV cache lets XLA update it in place instead of copying the
    full cache every decoded token.  Params are deliberately NOT donated:
    every decode step (and every subsequent ``generate`` call — fp vs cim
    comparisons serve the same params twice) reuses them.  CPU has no buffer
    donation; returning () there avoids a per-dispatch warning.
    """
    return (1,) if jax.default_backend() != "cpu" else ()


def make_decode_loop(cfg: ArchConfig, n_steps: int, *, greedy: bool = True):
    """Whole-generation decode as ONE ``lax.scan`` dispatch.

    Returns decode_loop(params, cache, tok0, key, prompt_len) ->
    (tokens (B, n_steps) i32, final cache).  The scan carries (cache, token,
    key); combined with cache donation the KV cache is updated in place for
    the entire generation — no per-token dispatch, no per-step cache copy.
    The sampling path and PRNG split schedule are identical to the eager
    per-token loop in ``launch.serve.generate``, so both loops emit the same
    tokens for the same seed.
    """

    def decode_loop(params, cache, tok0, key, prompt_len):
        params = _serving_params(params)  # hoisted above the token scan

        def body(carry, pos):
            cache, tok, key = carry
            logits, cache = api.decode_step(params, cfg, cache, tok, pos)
            if greedy:
                nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits[:, -1])[:, None].astype(jnp.int32)
            return (cache, nxt, key), nxt

        positions = prompt_len + jnp.arange(n_steps, dtype=jnp.int32)
        (cache, _, _), toks = jax.lax.scan(body, (cache, tok0, key), positions)
        # toks: (n_steps, B, 1) -> (B, n_steps)
        return jnp.swapaxes(toks[..., 0], 0, 1), cache

    return decode_loop


@jax.named_scope("sample")
def _row_pick(logits, keys, greedy, consume=None):
    """Per-row token pick — THE sampling path and PRNG split schedule shared
    by every ragged dispatch (decode loop, prefill chunk, fused step), so
    their streams stay bit-identical to the solo ``serve.generate`` pick.

    logits (B, S, V) — the last position samples; keys (B, 2); greedy (B,)
    bool — greedy rows take argmax and never consume randomness (matching
    the solo loop's schedule); ``consume`` optionally masks which sampled
    rows' keys really advance (rows whose pick the caller will discard —
    mid-prompt chunks, replayed tokens — must not burn a split).
    Returns (tok (B,) i32, keys_out (B, 2)).
    """
    greedy_tok = jnp.argmax(logits[:, -1], axis=-1)
    split = jax.vmap(jax.random.split)(keys)  # (B, 2, 2)
    keys_new, subs = split[:, 0], split[:, 1]
    sampled = jax.vmap(jax.random.categorical)(subs, logits[:, -1])
    tok = jnp.where(greedy, greedy_tok, sampled).astype(jnp.int32)
    advance = ~greedy if consume is None else (consume & ~greedy)
    keys_out = jnp.where(advance[:, None], keys_new, keys)
    return tok, keys_out


def _ragged_scan_body(params, cfg: ArchConfig, greedy):
    """The one decode-quantum scan body: ``make_paged_decode_loop`` and the
    fused step's decode sub-batch run this exact closure, so fused-vs-split
    is purely a scheduling difference.  Carry: (caches, tok (B, 1), keys,
    pos (B,)); emits each step's (B,) tokens."""

    def body(carry, _):
        caches, tok, keys, pos = carry
        logits, caches = api.decode_step(params, cfg, caches, tok, pos)
        nxt, keys = _row_pick(logits, keys, greedy)
        return (caches, nxt[:, None], keys, pos + 1), nxt

    return body


def make_paged_decode_loop(cfg: ArchConfig, n_steps: int, page_size: int):
    """Ragged continuous-batching decode quantum as ONE ``lax.scan`` dispatch.

    Returns decode_loop(params, pools, table (B, P) i32, state (B, 3) i32
    rows = [tok, pos, greedy], keys (B, 2) u32) ->
    (tokens (B, n_steps) i32, pools, keys (B, 2)).

    Same donated-cache scan structure as :func:`make_decode_loop`, but every
    slot carries its own position, PRNG key, and greedy flag: the KV write
    and attention mask are per-slot (paged pool + block table), and sampling
    splits each slot's key independently — so each row's token stream is
    bit-identical to a solo ``launch.serve.generate`` run of that request
    (rows are padded/retired independently; the host discards post-EOS
    tokens).  The block ``table`` must already cover positions up to
    ``pos + n_steps`` for every live row; padded rows point at the dummy
    page.
    """

    def decode_loop(params, pools, table, state, keys):
        params = _serving_params(params)  # hoisted above the token scan
        tok0 = state[:, 0:1]
        pos0 = state[:, 1]
        greedy = state[:, 2].astype(bool)
        # gather every slot's pages ONCE; the scan then runs the ordinary
        # contiguous-cache decode step (vector positions) against the view
        caches = api.paged_view(cfg, pools, table, page_size)
        (caches, _, keys, _), toks = jax.lax.scan(
            _ragged_scan_body(params, cfg, greedy),
            (caches, tok0, keys, pos0), None, length=n_steps,
        )
        # write back only the quantum's new cells, one scatter per dispatch
        pools = api.paged_writeback(cfg, pools, caches, table, pos0, n_steps, page_size)
        return jnp.swapaxes(toks, 0, 1), pools, keys

    return decode_loop


def make_fused_step(cfg: ArchConfig, n_steps: int, page_size: int):
    """Fused prefill+decode dispatch: ONE bucketed dispatch per engine cycle
    in which some rows are prefill chunks and others are decode quanta.

    Returns fused_step(params, pools,
        pf_table (Bp, P) i32, pf_tokens (Bp, C) i32, pf_meta (Bp, 5) i32,
        pf_keys (Bp, 2) u32,
        table (B, P) i32, state (B, 5) i32, keys (B, 2) u32, join (B,) i32)
    -> (pf_tok (Bp,) i32, toks (B, n_steps) i32, keys_out (B, 2), pools).

    Two sub-batches, one XLA computation, one host round trip:

      * **Chunk sub-batch** (prefill rows only, width C bucketed to the
        widest live chunk): exactly the ``make_prefill_chunk_step`` compute —
        ``pf_meta`` rows are [start, kv_len, last_idx, greedy, consume];
        ``pf_tok`` samples each row's next token in-graph (``consume``
        marks rows whose PRNG key this pick really advances: final-chunk
        rows that are not replaying an already-emitted token).
      * **Decode sub-batch** (decode rows + rows whose prompt finishes in
        this very dispatch): exactly the ``make_paged_decode_loop`` scan —
        ``state`` rows are [tok, pos, greedy, tok_override, use_override].
        ``join`` maps each scan row to its chunk row (-1 for plain decode
        rows): a finishing row's scan seeds from its in-graph first token
        ``pf_tok[join]`` and continuation key — it rolls straight from
        prefill into an ``n_steps``-token decode quantum *inside the same
        dispatch*, no dead cycle between phases.  ``use_override`` rows
        (recompute re-admissions replaying prompt+generated) seed from
        ``tok_override`` — the token they emitted before preemption —
        without consuming PRNG: its sampling already happened once.

    Keeping the two sub-batches separate (rather than widening every row to
    the chunk width) means decode rows pay exactly the decode-loop compute,
    the chunk stage runs at its own (usually much smaller) row bucket, and
    both stages are literally the same code the split dispatches run —
    ``_row_pick`` and ``_ragged_scan_body`` are shared with
    :func:`make_paged_decode_loop` / :func:`make_prefill_chunk_step` — so
    fused-vs-split is purely a scheduling difference and every row's token
    stream stays bit-identical to a solo ``launch.serve.generate`` run
    (pinned in tests/test_engine.py).  The scan's view is gathered after the
    chunk write-back, so a finishing row's prompt KV is visible to its own
    decode steps.
    """

    def fused_step(params, pools, pf_table, pf_tokens, pf_meta, pf_keys,
                   table, state, keys, join):
        params = _serving_params(params)

        # ---- chunk sub-batch: one prefill chunk per prefilling row --------
        start, kv_len, last_idx = pf_meta[:, 0], pf_meta[:, 1], pf_meta[:, 2]
        pf_greedy = pf_meta[:, 3].astype(bool)
        pf_consume = pf_meta[:, 4].astype(bool)
        caches = api.paged_view(cfg, pools, pf_table, page_size)
        logits, caches = api.chunk_on_views(
            params, cfg, caches, pf_tokens, start, kv_len, last_idx
        )
        pf_tok, pf_keys_out = _row_pick(logits, pf_keys, pf_greedy, consume=pf_consume)
        bp, c = pf_tokens.shape
        start_b = jnp.broadcast_to(jnp.atleast_1d(start), (bp,))
        pools = api.paged_writeback(cfg, pools, caches, pf_table, start_b, c, page_size)

        # ---- decode quantum: decode rows + just-finished prefill rows -----
        use_join = join >= 0
        jidx = jnp.clip(join, 0)
        tok0 = jnp.where(use_join, pf_tok[jidx], state[:, 0])
        tok0 = jnp.where(state[:, 4].astype(bool), state[:, 3], tok0)[:, None]
        keys0 = jnp.where(use_join[:, None], pf_keys_out[jidx], keys)
        pos0 = state[:, 1]
        greedy = state[:, 2].astype(bool)
        caches = api.paged_view(cfg, pools, table, page_size)
        (caches, _, keys_out, _), toks = jax.lax.scan(
            _ragged_scan_body(params, cfg, greedy),
            (caches, tok0, keys0, pos0), None, length=n_steps,
        )
        pools = api.paged_writeback(cfg, pools, caches, table, pos0, n_steps, page_size)
        return pf_tok, jnp.swapaxes(toks, 0, 1), keys_out, pools

    return fused_step


def make_prefill_chunk_step(cfg: ArchConfig, page_size: int):
    """One chunked-prefill dispatch, B requests wide, first-token sampling
    fused in.

    (params, pools, table (B, P), tokens (B, C), meta (B, 4) i32 rows =
    [start, kv_len, last_idx, greedy], keys (B, 2) u32) ->
    (tok (B,) i32, keys_out (B, 2), pools).

    ``meta`` is traced, so one compiled variant serves every chunk of a
    given (B, C, P) bucket; ``tok[r]`` is only meaningful on row r's final
    chunk (earlier chunks sample from a mid-prompt position and the caller
    ignores them — a row's key is only adopted when the caller accepts the
    token, keeping the PRNG schedule identical to the solo pick)."""

    def chunk_step(params, pools, table, tokens, meta, keys):
        params = _serving_params(params)
        start, kv_len, last_idx = meta[:, 0], meta[:, 1], meta[:, 2]
        greedy = meta[:, 3].astype(bool)
        logits, pools = api.prefill_chunk(
            params, cfg, pools, table, tokens, start, kv_len, last_idx, page_size
        )
        tok, keys_out = _row_pick(logits, keys, greedy)
        return tok, keys_out, pools

    return chunk_step
