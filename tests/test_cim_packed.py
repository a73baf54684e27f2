"""Bit-plane-native serving: packed kernel parity + operand export + serve.

Pins the packed serving contract end to end:
  * the packed Pallas kernel (interpret mode) against the packed reference,
    the int8-plane kernel modes, and the dense quantized matmul — across both
    encodings, odd K not divisible by 8, and degenerate decode shapes;
  * operand export: ``deploy_params(materialize=...)`` re-encodings are exact
    (same achieved weights as the dense materialization, stucking included);
  * serving: packed/int8 deployments generate bit-identical tokens to the
    dense deployment, and the scan decode loop matches the python loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import bitslice, simulator
from repro.core.planner import CrossbarSpec, PlannerConfig, build_deployment, deploy_params
from repro.kernels.cim_matmul import ops as cm_ops, ref as cm_ref
from repro.launch.serve import generate
from repro.models import api, layers


def _packed_operands(w, cols, encoding="sign_magnitude"):
    qt = bitslice.quantize(w, cols, encoding)
    q = qt.q.reshape(w.shape)
    sign = qt.sign.reshape(w.shape)
    return (
        bitslice.pack_linear_planes(q, cols),
        bitslice.pack_linear_sign(sign),
        qt,
    )


# ---------------------------------------------------------------------------
# Kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m,k,n,cols,x_kind",
    [
        pytest.param(4, 32, 16, 4, "f32", id="4-32-16-4"),
        pytest.param(17, 100, 60, 8, "f32", id="17-100-60-8"),  # K not divisible by 8
        pytest.param(128, 128, 128, 10, "f32", id="128-128-128-10"),
        pytest.param(1, 7, 3, 10, "f32", id="1-7-3-10"),  # degenerate decode shapes
        pytest.param(3, 9, 130, 6, "f32", id="3-9-130-6"),
        pytest.param(8, 1, 1, 2, "f32", id="8-1-1-2"),
        pytest.param(300, 40, 5, 10, "f32", id="300-40-5-10"),  # M larger than one chunk-of-8
        # several 2048 x 512 tiles along K and N, ragged tails on both
        (8, 4046, 1466, 10, "f32"),
        (8, 4046, 1466, 10, "bf16"),
        (16, 4046, 1466, 8, "bf16"),  # cols 8: no second integer part
        (5, 300, 200, 2, "bf16"),
        (24, 2000, 700, 10, "int_bf16"),  # exact sums: bit-equal to the reference
    ],
)
def test_packed_kernel_vs_ref(m, k, n, cols, x_kind):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 7 + n))
    if x_kind == "int_bf16":
        x = jnp.round(jax.random.uniform(kx, (m, k), minval=-4, maxval=4)).astype(jnp.bfloat16)
    else:
        x = jax.random.normal(kx, (m, k)).astype(jnp.bfloat16 if x_kind == "bf16" else jnp.float32)
    w = jax.random.normal(kw, (k, n)) * 0.1
    pp, sp, qt = _packed_operands(w, cols)
    got = cm_ops.cim_matmul_packed(x, pp, sp, qt.scale, interpret=True)
    want = cm_ref.cim_matmul_packed(x, pp, sp, qt.scale)
    if x_kind == "int_bf16":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and against the dense quantized matmul (the end-to-end contract)
    w_hat = bitslice.dequantize(qt).reshape(w.shape)
    np.testing.assert_allclose(got, x.astype(jnp.float32) @ w_hat, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_skip_twin_matches_plain(dtype, key):
    """The zero-chunk skip twin equals the plain kernel on the same operands,
    bit for bit, with chunks of every kind: all planes zero, only the low
    8 planes live, and both groups live."""
    kx, kw = jax.random.split(key)
    k, n, cols = 2048, 640, 10
    w = jax.random.normal(kw, (k, n)) * 0.1
    # rows 0-511 zero, rows 512-1023 small (planes 8-9 zero), the rest full range
    w = w * jnp.where(jnp.arange(k) < 512, 0.0, jnp.where(jnp.arange(k) < 1024, 0.01, 1.0))[:, None]
    pp, sp, qt = _packed_operands(w, cols)
    t = np.asarray(pp).reshape(cols, k // 128, 16, n).any(axis=(2, 3)).astype(np.uint8)
    live = np.asarray(cm_ops._live_groups(jnp.asarray(t), cols, k))
    assert {0, 1, 2} <= set(live.tolist()), live
    x = jax.random.normal(kx, (8, k)).astype(dtype)
    with_skip = cm_ops.cim_matmul_packed(x, pp, sp, qt.scale, tile_nz=t, interpret=True)
    without = cm_ops.cim_matmul_packed(x, pp, sp, qt.scale, interpret=True)
    np.testing.assert_array_equal(np.asarray(with_skip), np.asarray(without))


@pytest.mark.parametrize("mode", ["fused_dequant", "planes"])
def test_packed_kernel_vs_int8_modes(mode, key):
    kx, kw = jax.random.split(key)
    m, k, n, cols = 8, 96, 48, 10
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n)) * 0.1
    pp, sp, qt = _packed_operands(w, cols)
    q = qt.q.reshape(w.shape)
    sign = qt.sign.reshape(w.shape)
    splanes = jnp.moveaxis(bitslice.bitplanes(q, cols).astype(jnp.int8) * sign[..., None], -1, 0)
    got = cm_ops.cim_matmul_packed(x, pp, sp, qt.scale, interpret=True)
    want = cm_ops.cim_matmul(x, splanes, qt.scale, mode=mode, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_packed_kernel_m_chunking(key):
    """M chunking concatenates cleanly (chunk boundary not an M multiple)."""
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (67, 40))
    w = jax.random.normal(kw, (40, 24)) * 0.1
    pp, sp, qt = _packed_operands(w, 6)
    got = cm_ops.cim_matmul_packed(x, pp, sp, qt.scale, m_chunk=16, interpret=True)
    want = cm_ref.cim_matmul_packed(x, pp, sp, qt.scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(1, 1, 1), (2, 3, 5), (5, 8, 8), (8, 130, 7)])
def test_int8_kernel_degenerate_shapes(m, k, n):
    """Tiny decode shapes through the int8 kernel path (block clamp fix)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(m + 10 * k + 100 * n))
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n)) * 0.1
    qt = bitslice.quantize(w, 6)
    q = qt.q.reshape(w.shape)
    sign = qt.sign.reshape(w.shape)
    sp8 = jnp.moveaxis(bitslice.bitplanes(q, 6).astype(jnp.int8) * sign[..., None], -1, 0)
    got = cm_ops.cim_matmul(x, sp8, qt.scale)
    want = cm_ref.cim_matmul(x, sp8, qt.scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_block_clamp_non_hardware_block_sizes(key):
    """Caller-supplied block sizes that aren't tile multiples are normalized
    (the seed clamp could emit a bm not divisible by 8)."""
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (20, 70))
    w = jax.random.normal(kw, (70, 33)) * 0.1
    qt = bitslice.quantize(w, 4)
    q = qt.q.reshape(w.shape)
    sign = qt.sign.reshape(w.shape)
    sp8 = jnp.moveaxis(bitslice.bitplanes(q, 4).astype(jnp.int8) * sign[..., None], -1, 0)
    got = cm_ops.cim_matmul(x, sp8, qt.scale, bm=20, bn=100, bk=100)
    want = cm_ref.cim_matmul(x, sp8, qt.scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    pp, sp = bitslice.pack_linear_planes(q, 4), bitslice.pack_linear_sign(sign)
    # the packed kernel takes no block sizes: it sizes its tiles from the shapes
    bk, bn = cm_ops.packed_tiles(24, 70, 33, 4, 4)
    assert (bk % 256, bn % 128) == (0, 0)
    got_p = cm_ops.cim_matmul_packed(x, pp, sp, qt.scale, interpret=True)
    np.testing.assert_allclose(got_p, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("encoding", ["sign_magnitude", "offset_binary"])
def test_cim_linear_packed_both_encodings(encoding, key):
    """Packed operands through cim_linear (rank-1 offset correction included)."""
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (4, 64))
    w = jax.random.normal(kw, (64, 32)) * 0.1 + 0.05
    spec = CrossbarSpec(rows=128, cols=10, encoding=encoding)
    ops_p = simulator.prepare_linear(w, spec, materialize="packed")
    y = simulator.cim_linear(x, ops_p)
    w_hat = bitslice.dequantize(bitslice.quantize(w, 10, encoding)).reshape(w.shape)
    np.testing.assert_allclose(y, x @ w_hat, rtol=1e-4, atol=1e-4)
    # int8 materialization of the same weight agrees
    y8 = simulator.cim_linear(x, simulator.prepare_linear(w, spec))
    np.testing.assert_allclose(y, y8, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Operand export (deploy_params materializations)
# ---------------------------------------------------------------------------

def test_operands_from_dense_bit_exact_planes(key):
    """Packed operands recovered from dense w_hat equal the ones built from
    the quantizer's own q — bit for bit, both encodings, stucking included."""
    w = jax.random.normal(key, (96, 40)) * 0.1
    for encoding in ("sign_magnitude", "offset_binary"):
        qt = bitslice.quantize(w, 10, encoding)
        w_hat = bitslice.dequantize(qt).reshape(w.shape)
        got = simulator.operands_from_dense(w_hat, qt.scale, qt.offset, encoding, 10)
        q = qt.q.reshape(w.shape)
        sign = qt.sign.reshape(w.shape)
        np.testing.assert_array_equal(got["planes_packed"], bitslice.pack_linear_planes(q, 10))
        np.testing.assert_array_equal(got["sign_packed"], bitslice.pack_linear_sign(sign))


def test_densify_packed_roundtrip(key):
    w = jax.random.normal(key, (40, 24)) * 0.1
    qt = bitslice.quantize(w, 10)
    w_hat = bitslice.dequantize(qt).reshape(w.shape)
    op = simulator.operands_from_dense(w_hat, qt.scale, qt.offset, "sign_magnitude", 10)
    np.testing.assert_allclose(simulator.densify_operands(op), w_hat, rtol=1e-6, atol=1e-7)
    # pytree walk: nested params with dense leaves left alone
    tree = {"a": {"w": op}, "b": w}
    out = simulator.densify_packed(tree)
    assert out["b"] is w and not simulator.is_cim_operands(out["a"]["w"])


def test_layers_linear_batched_operands(key):
    """Stacked (expert/layer) operand dicts vmap against stacked activations."""
    kw, kx = jax.random.split(key)
    w = jax.random.normal(kw, (3, 32, 16)) * 0.1  # [E, K, N]
    x = jax.random.normal(kx, (3, 5, 32))  # [E, cap, K]
    qt = bitslice.quantize(w, 10)
    w_hat = bitslice.dequantize(qt).reshape(w.shape)
    op = simulator.operands_from_dense(w_hat, qt.scale, qt.offset, "sign_magnitude", 10)
    y = layers.linear(op, x, jnp.float32)
    np.testing.assert_allclose(y, x @ w_hat, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Serving end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deployed_gemma():
    cfg = get_arch("gemma-2b", reduced=True)
    key = jax.random.PRNGKey(0)
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, 2, 12)
    plan = build_deployment(
        params, CrossbarSpec(rows=128, cols=10),
        PlannerConfig(p_stuck=0.5, min_size=1024),
    )
    return cfg, params, batch, plan


def test_serve_packed_tokens_match_dense(deployed_gemma):
    """The acceptance contract: packed / int8 materializations generate
    bit-identical tokens to the dense-materialized deployment."""
    cfg, params, batch, plan = deployed_gemma
    toks = {}
    for mat in ("dense", "packed", "planes_int8"):
        p = deploy_params(params, plan, materialize=mat)
        toks[mat], _ = generate(cfg, p, batch, gen_len=6)
    np.testing.assert_array_equal(toks["dense"], toks["packed"])
    np.testing.assert_array_equal(toks["dense"], toks["planes_int8"])


@pytest.mark.parametrize("codec", ["const_rle", "col_perm", "col_perm_rle"])
def test_serve_codec_tokens_match_dense(deployed_gemma, codec):
    """ISSUE acceptance: the serve token stream is bit-identical to dense
    for every plane codec (codec-encoded operand dicts decode exactly)."""
    cfg, params, batch, plan = deployed_gemma
    toks_dense, _ = generate(cfg, deploy_params(params, plan), batch, gen_len=6)
    p = deploy_params(params, plan, materialize="packed", codec=codec)
    toks, _ = generate(cfg, p, batch, gen_len=6)
    np.testing.assert_array_equal(toks_dense, toks)


def test_serve_planner_codec_end_to_end(key):
    """Full pipeline with the codec in the *planner* (col_perm_rle physical
    storage) — deployed weights and forward logits match the raw-codec plan."""
    cfg = get_arch("gemma-2b", reduced=True)
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, 2, 8)
    spec = CrossbarSpec(rows=128, cols=10)
    plan_raw = build_deployment(params, spec, PlannerConfig(p_stuck=1.0, min_size=1024))
    plan_enc = build_deployment(
        params, spec, PlannerConfig(p_stuck=1.0, min_size=1024, codec="col_perm_rle")
    )
    la, _ = api.forward(deploy_params(params, plan_raw), cfg, batch)
    lb, _ = api.forward(deploy_params(params, plan_enc), cfg, batch)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    t_raw = sum(r.transitions_sws for r in plan_raw.reports.values())
    t_enc = sum(r.transitions_sws for r in plan_enc.reports.values())
    assert t_enc <= t_raw


def test_serve_scan_matches_python_loop(deployed_gemma):
    cfg, params, batch, plan = deployed_gemma
    p = deploy_params(params, plan, materialize="packed")
    for greedy in (True, False):
        a, _ = generate(cfg, p, batch, gen_len=6, greedy=greedy, seed=7, loop="scan")
        b, _ = generate(cfg, p, batch, gen_len=6, greedy=greedy, seed=7, loop="python")
        np.testing.assert_array_equal(a, b)


def test_forward_packed_logits_close(deployed_gemma):
    cfg, params, batch, plan = deployed_gemma
    la, _ = api.forward(deploy_params(params, plan), cfg, batch)
    lb, _ = api.forward(deploy_params(params, plan, materialize="packed"), cfg, batch)
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-4)


def test_moe_forward_packed_matches_dense(key):
    """Expert-stacked weights route through the vmapped operand path."""
    cfg = get_arch("qwen2-moe-a2.7b", reduced=True)
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, 2, 8)
    plan = build_deployment(
        params, CrossbarSpec(rows=128, cols=10), PlannerConfig(p_stuck=1.0, min_size=512)
    )
    la, _ = api.forward(deploy_params(params, plan), cfg, batch)
    lb, _ = api.forward(deploy_params(params, plan, materialize="packed"), cfg, batch)
    np.testing.assert_allclose(la, lb, rtol=2e-4, atol=2e-4)


def test_decode_step_consumes_packed_operands(deployed_gemma):
    """Per-step decode computes directly on operand dicts (the TPU serving
    dataflow — no densify hop in between)."""
    cfg, params, batch, plan = deployed_gemma
    b = batch["tokens"].shape[0]
    cache_d = api.init_cache(cfg, b, 4)
    cache_p = api.init_cache(cfg, b, 4)
    tok = batch["tokens"][:, :1]
    la, _ = api.decode_step(deploy_params(params, plan), cfg, cache_d, tok, jnp.int32(0))
    lb, _ = api.decode_step(
        deploy_params(params, plan, materialize="packed"), cfg, cache_p, tok, jnp.int32(0)
    )
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # full reduced-model deploy + two forwards per family
@pytest.mark.parametrize(
    "arch", ["deepseek-v2-236b", "xlstm-350m", "hymba-1.5b", "seamless-m4t-medium"]
)
def test_families_forward_packed_matches_dense(arch, key):
    """Every model family's routed matmul sites accept packed operands
    (MATERIALIZE_DENSE_ONLY covers the non-matmul consumers)."""
    cfg = get_arch(arch, reduced=True)
    params = api.init(key, cfg)
    batch = api.make_batch(cfg, key, 2, 8)
    plan = build_deployment(
        params, CrossbarSpec(rows=128, cols=10), PlannerConfig(p_stuck=1.0, min_size=512)
    )
    la, _ = api.forward(deploy_params(params, plan), cfg, batch)
    lb, _ = api.forward(deploy_params(params, plan, materialize="packed"), cfg, batch)
    np.testing.assert_allclose(la, lb, rtol=2e-4, atol=2e-4)
