"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode runs these kernels on the CPU but never asks Mosaic (the
TPU kernel compiler) whether it accepts them: a block layout it cannot
lower (the Hamming kernel's old 1-D reduction output) or a tile that does
not fit VMEM passes every interpret-mode test and fails on the chip.  The
TPU compiler is installed here and compiles for a chip that is described
rather than attached, so these tests compile each kernel at the widths the
full-size deployment uses (internlm2-1.8b: d_model 2048, KV width 1024,
d_ff 8192, vocab 92544; 128x10 crossbar sections) and check that the
compiled program calls the kernel.  Nothing runs: this says nothing about
results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cim_matmul import ops as cim_ops
from repro.kernels.hamming import ops as hamming_ops

COLS = 10
# (K, N) of every packed linear of internlm2-1.8b: q/o, k/v, gate/up, down, head
SHAPES = [(2048, 2048), (2048, 1024), (2048, 8192), (8192, 2048), (2048, 92544)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(kernel: str, fn, *args) -> list[str]:
    """Compile ``fn``, check that it calls the Pallas kernel ``kernel`` and
    return the HLO lines of those calls."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [
        line.strip().removeprefix("ROOT ") for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    names = [line.split(" = ", 1)[0] for line in calls]
    assert any(name.startswith(f"%{kernel}") for name in names), (kernel, names)
    return [line for line in calls if line.startswith(f"%{kernel}")]


@pytest.mark.parametrize("k,n", SHAPES)
@pytest.mark.parametrize("m", [8, 256])
def test_cim_matmul_packed_compiles_for_v5e(one_chip, m, k, n):
    """Decode (M=8) and prefill-chunk (M=256) rows against packed planes."""
    _compile(
        "cim_matmul_packed_kernel",
        lambda x, p, s: cim_ops.cim_matmul_packed(x, p, s, 0.5, interpret=False),
        _spec(one_chip, (m, k), jnp.bfloat16),
        _spec(one_chip, (COLS, k // 8, n), jnp.uint8),
        _spec(one_chip, (k // 8, n), jnp.uint8),
    )


@pytest.mark.parametrize("m,k,n,dtype", [
    (8, 2048, 92544, jnp.float32),  # the LM head: float32 activations
    (8, 8192, 2048, jnp.bfloat16),
    (256, 2048, 8192, jnp.bfloat16),
])
def test_cim_matmul_packed_call_parses_for_roofline(one_chip, m, k, n, dtype):
    """The compiled kernel call still shows ``x[M, K]``, ``u8[cols, K/8, N]``
    and ``u8[K/8, N]``: the benchmark's roofline reader
    (``bench/roofline/cim_matmul.parse_call``) reads the call's shapes from
    them, and a call it cannot read silences the roofline share."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import common

    roofline = common.load_module("roofline", "cim_matmul")
    calls = _compile(
        "cim_matmul_packed_kernel",
        lambda x, p, s: cim_ops.cim_matmul_packed(x, p, s, 0.5, interpret=False),
        _spec(one_chip, (m, k), dtype),
        _spec(one_chip, (COLS, k // 8, n), jnp.uint8),
        _spec(one_chip, (k // 8, n), jnp.uint8),
    )
    itemsize = jnp.dtype(dtype).itemsize
    assert [roofline.parse_call(line) for line in calls] == [(m, k, n, COLS, itemsize)]


def test_cim_matmul_packed_compiles_under_highest_precision(one_chip):
    """A caller's ``default_matmul_precision("highest")`` reaches the kernel's
    dots; the bfloat16 parts are exact, so their dots keep one pass (Mosaic
    refuses bfloat16 operands at float32 precision)."""
    with jax.default_matmul_precision("highest"):
        _compile(
            "cim_matmul_packed_kernel",
            lambda x, p, s: cim_ops.cim_matmul_packed(x, p, s, 0.5, interpret=False),
            _spec(one_chip, (8, 2048), jnp.bfloat16),
            _spec(one_chip, (COLS, 256, 2048), jnp.uint8),
            _spec(one_chip, (256, 2048), jnp.uint8),
        )


def test_cim_matmul_packed_skip_compiles_for_v5e(one_chip):
    """The zero-tile skip twin (const_rle operands) at the MLP down shape."""
    m, (k, n) = 256, SHAPES[3]
    _compile(
        "cim_matmul_packed_skip_kernel",
        lambda x, p, s, nz: cim_ops.cim_matmul_packed(
            x, p, s, 0.5, interpret=False, tile_nz=nz
        ),
        _spec(one_chip, (m, k), jnp.bfloat16),
        _spec(one_chip, (COLS, k // 8, n), jnp.uint8),
        _spec(one_chip, (k // 8, n), jnp.uint8),
        _spec(one_chip, (COLS, k // 8 // 16), jnp.uint8),
    )


def test_hamming_pairs_compiles_for_v5e(one_chip):
    """The planner's pricing kernel on 4096 pairs of 128x10 sections."""
    a = _spec(one_chip, (4096, 16, COLS), jnp.uint8)
    _compile(
        "hamming_pairs_kernel",
        lambda x, y: hamming_ops.hamming_pairs(x, y, interpret=False), a, a,
    )


def _kernel_calls(one_chip):
    """(kernel name, raw entry without its jit, operand specs) of every Pallas
    kernel in ``repro/kernels``."""
    from repro.kernels.bitslice import kernel as bitslice_k
    from repro.kernels.cim_matmul import kernel as cim_k
    from repro.kernels.flash_attention import kernel as flash_k
    from repro.kernels.hamming import kernel as hamming_k

    u8, f32, bf16, i32 = jnp.uint8, jnp.float32, jnp.bfloat16, jnp.int32
    s = functools.partial(_spec, one_chip)
    return {
        "cim_matmul_packed_kernel": (
            cim_k.cim_matmul_packed_kernel,
            (s((8, 2048), f32), s((COLS, 256, 1024), u8), s((256, 1024), u8))),
        "cim_matmul_packed_skip_kernel": (
            cim_k.cim_matmul_packed_skip_kernel,
            (s((8, 2048), f32), s((COLS, 256, 1024), u8), s((256, 1024), u8),
             s((2048 // 256,), i32))),
        "cim_matmul_kernel": (
            cim_k.cim_matmul_kernel, (s((128, 256), f32), s((COLS, 256, 256), jnp.int8))),
        "hamming_pairs_kernel": (
            hamming_k.hamming_pairs_kernel, (s((4096, 16, COLS), u8), s((4096, 16, COLS), u8))),
        "bitslice_kernel": (
            functools.partial(bitslice_k.bitslice_kernel.__wrapped__, cols=COLS),
            (s((256, 256), f32), s((), f32))),
        "flash_attention_kernel": (
            flash_k.flash_attention_kernel,
            (s((1, 2, 128, 128), bf16), s((1, 1, 128, 128), bf16), s((1, 1, 128, 128), bf16),
             s((1,), i32), s((1,), i32))),
    }


@pytest.mark.parametrize("kernel", [
    "cim_matmul_packed_kernel", "cim_matmul_packed_skip_kernel", "cim_matmul_kernel",
    "hamming_pairs_kernel", "bitslice_kernel", "flash_attention_kernel",
])
def test_kernel_keeps_its_name_without_its_python_function(one_chip, kernel):
    """A profiler trace names a kernel's device op after the kernel: the
    benchmark's roofline readers find ``cim_matmul_packed_kernel`` and
    ``hamming_pairs_kernel`` by it.  Each ``pallas_call`` names itself, so
    its raw body, called outside the jitted function that bears the same
    name (as after a rename of that function), compiles to a call of that
    name still."""
    entry, args = _kernel_calls(one_chip)[kernel]
    raw = getattr(entry, "__wrapped__", entry)
    _compile(kernel, lambda *a: raw(*a), *args)
