"""The trace reduction, on small traces: busy union, idle share, kernel
time by name, idle stretches by host span."""
import json
import os

import pytest

from bench import common
from bench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_busy_union_and_idle_share():
    tr = _load("trace_synthetic.json")
    # busy: [0, 250) (three overlapping ops, one clipped at the window's
    # start), [400, 500), [900, 950), [990, 1000) (clipped at its end)
    assert T.window_s(tr) == pytest.approx(1e-6)
    assert T.busy_s(tr) == pytest.approx(410e-9)
    idle = common.load_module("metrics", "device_idle.serve").read({"trace": tr})
    assert idle == pytest.approx(59.0)


def test_op_time_by_name():
    ops = T.op_seconds(_load("trace_synthetic.json"))
    assert ops["fusion"] == pytest.approx((20 + 80 + 100) * 1e-9)
    assert ops["cim_matmul_packed_kernel"] == pytest.approx(200e-9)
    assert ops["copy"] == pytest.approx(60e-9)
    calls = T.kernel_calls(_load("trace_synthetic.json"), "cim_matmul_packed")
    assert len(calls) == 1 and calls[0][0] == pytest.approx(200e-9)


def test_idle_by_host_span():
    gaps = dict(T.idle_gaps(_load("trace_synthetic.json")))
    # idle [250, 400) and [500, 600) inside bench.step, [600, 900) and
    # [950, 990) inside bench.wait_arrival
    assert gaps["bench.step"] == pytest.approx(250e-9)
    assert gaps["bench.wait_arrival"] == pytest.approx(340e-9)
    assert "host.other" not in gaps
    b = T.breakdown(_load("trace_synthetic.json"))
    assert b["device_ops"][0][0] == "fusion" and len(b["idle_gaps"]) == 2


def test_roofline_share_from_the_trace():
    tr = _load("trace_synthetic.json")
    ctx = {"trace": tr, "peaks": common.peaks_for("TPU v5 lite")}
    share = common.load_module("metrics", "cim_matmul_roofline.serve").read(ctx)
    least = 5_865_472 / 819e9  # memory-bound: M=8 decode call
    assert share == pytest.approx(100 * least / 200e-9)
    tr["device"]["/device:TPU:0"][2][3] = ""  # shapes unreadable: no share
    assert common.load_module("metrics", "cim_matmul_roofline.serve").read(ctx) is None


def test_no_kernel_no_share():
    tr = _load("trace_synthetic.json")
    ctx = {"trace": tr, "peaks": common.peaks_for("TPU v5 lite")}
    assert common.load_module("metrics", "hamming_roofline.plan").read(ctx) is None


def test_recorded_v5e_trace():
    """3 ms of a chat-internlm2 trace recorded on a TPU v5e: op events are
    named by their HLO text, the packed matmul by its kernel."""
    tr = _load("trace_v5e_chat.json")
    assert 0 < T.busy_s(tr) <= T.window_s(tr) == pytest.approx(3e-3)
    calls = T.kernel_calls(tr, "cim_matmul_packed")
    assert len(calls) == 1
    rl = common.load_module("roofline", "cim_matmul")
    assert rl.parse_call(calls[0][1]) == (256, 2048, 1024, 10, 2)  # prefill M, wk
    ctx = {"trace": tr, "peaks": common.peaks_for("TPU v5 lite")}
    share = common.load_module("metrics", "cim_matmul_roofline.serve").read(ctx)
    assert 0 < share <= 100
    assert all(n not in T.CONTAINERS for n, _ in T.breakdown(tr)["device_ops"])
