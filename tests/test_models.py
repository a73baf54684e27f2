"""Per-arch smoke tests + decode/forward parity (the serving-correctness test)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.launch.steps import make_train_step
from repro.models import api
from repro.optim import AdamWConfig, adamw_init

ARCHS = list_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_no_nan(arch, key):
    cfg = get_arch(arch, reduced=True)
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, 2, 16)
    logits, aux = api.forward(params, cfg, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch, key):
    cfg = get_arch(arch, reduced=True)
    params = api.init(key, cfg)
    opt_state = adamw_init(params)
    batch = api.make_batch(cfg, key, 2, 16)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3), remat="none"))
    new_params, new_opt, metrics = step(params, opt_state, batch)
    assert float(metrics["loss"]) > 0 and not np.isnan(float(metrics["loss"]))
    # params actually moved
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)), params, new_params)
    assert any(jax.tree.leaves(moved))


@pytest.mark.parametrize("arch", ARCHS)
def test_remat_matches_no_remat(arch, key):
    cfg = get_arch(arch, reduced=True)
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, 2, 16)
    l0, _ = api.forward(params, cfg, batch, remat="none")
    l1, _ = api.forward(params, cfg, batch, remat="full")
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch, key):
    """prefill(prompt) + decode_step x G reproduces forward() logits.

    This is the fundamental serving-correctness invariant: the incremental
    path (KV caches, ring buffers, recurrent states, absorbed MLA matmuls)
    must match the parallel training path position by position.
    """
    cfg = get_arch(arch, reduced=True)
    if cfg.moe is not None:
        # capacity-style dispatch may drop tokens under load in the parallel
        # path but never in single-token decode; parity is only defined in
        # the drop-free regime, so give the test headroom.
        import dataclasses

        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    b, prompt, gen = 2, 12, 4
    total = prompt + gen
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, b, total)

    full_logits, _ = api.forward(params, cfg, batch)

    # prefill on the prompt prefix
    pf_batch = dict(batch, tokens=batch["tokens"][:, :prompt])
    if cfg.encdec:
        pf_batch["src_embeds"] = batch["src_embeds"][:, :prompt]
        # the encoder context differs between the two paths unless we feed the
        # same src length; re-run the full path with the prompt-length source
        full_logits, _ = api.forward(
            params, cfg, dict(batch, src_embeds=pf_batch["src_embeds"])
        )
    logits_pf, pf_cache = api.prefill(params, cfg, pf_batch)

    cache = api.init_cache(cfg, b, total, src_len=prompt if cfg.encdec else None)
    cache = api.merge_prefill_cache(cfg, cache, pf_cache)

    np.testing.assert_allclose(
        logits_pf[:, -1], full_logits[:, prompt - 1], rtol=2e-4, atol=2e-4
    )

    for i in range(gen):
        tok = batch["tokens"][:, prompt + i : prompt + i + 1]
        logits_i, cache = api.decode_step(params, cfg, cache, tok, jnp.int32(prompt + i))
        np.testing.assert_allclose(
            logits_i[:, 0], full_logits[:, prompt + i], rtol=2e-4, atol=2e-4,
            err_msg=f"{arch}: decode step {i} diverged from forward",
        )


def test_gqa_grouping_matches_repeated_kv(key):
    """blockwise_attention's query-grouping equals the repeat-KV formulation."""
    from repro.models.attention import blockwise_attention

    ks = jax.random.split(key, 3)
    b, hq, hkv, s, d = 2, 8, 2, 32, 16
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    grouped = blockwise_attention(q, k, v, kind="causal", block_k=16)
    rep = hq // hkv
    full = blockwise_attention(
        q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1), kind="causal", block_k=16
    )
    np.testing.assert_allclose(grouped, full, rtol=1e-5, atol=1e-5)


def test_param_counts_active_vs_total():
    cfg = get_arch("qwen2-moe-a2.7b", reduced=True)
    params = api.init(jax.random.PRNGKey(0), cfg)
    total = api.param_count(params)
    active = api.active_param_count(params, cfg)
    assert active < total  # MoE: most experts inactive per token


def test_reference_matches_model_forward(key):
    """The plain float32 reference (models/reference.py) and the model's own
    forward agree on logits for the dense GQA decoder at a small size."""
    from repro.models import reference

    cfg = get_arch("internlm2-1.8b", reduced=True)  # float32 compute
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, 2, 24)
    with jax.default_matmul_precision("highest"):
        want, _ = api.forward(params, cfg, batch)
    got = reference.logits(params, cfg, batch["tokens"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # bfloat16 matmul inputs move the logits, but only by rounding
    low = reference.logits(params, cfg, batch["tokens"], dtype=jnp.bfloat16)
    rel = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert 0 < rel < 5e-2


def test_reference_rejects_other_architectures():
    from repro.models import reference

    cfg = get_arch("qwen2-moe-a2.7b", reduced=True)
    with pytest.raises(NotImplementedError):
        reference.logits({}, cfg, np.zeros((1, 4), np.int32))
