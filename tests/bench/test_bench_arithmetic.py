"""End-to-end arithmetic of the drivers and the roofline counts, against
hand counts."""
import time

import numpy as np
import pytest

from bench import common

serve = common.load_module("drivers", "serve")
plan = common.load_module("drivers", "plan")
cim = common.load_module("roofline", "cim_matmul")
ham = common.load_module("roofline", "hamming")
gqa = common.load_module("roofline", "dense_gqa")


def _rec(due, first, done, n, ok=True):
    return {"rid": 0, "due": due, "first": first, "done": done if ok else None,
            "tokens": [1] * n, "max_new": n, "ok": ok, "prompt": np.zeros(4, np.int32),
            "admitted": due}


def test_percentile_is_nearest_rank():
    xs = list(range(1, 11))
    assert common.percentile(xs, 90) == 9
    assert common.percentile(xs, 50) == 5
    assert common.percentile([3.0], 90) == 3.0
    assert common.percentile(list(range(1, 101)), 90) == 90


def test_serving_rates_and_tails():
    # ten requests due 1 s apart; one never finishes
    recs = [_rec(i, i + 0.1 * (i + 1), i + 2.0, 11) for i in range(9)]
    recs.append(_rec(9.0, None, None, 11, ok=False))
    win = {"records": recs, "emitted": 400, "seconds": 10.0, "end": 70.0}
    e2e = serve.end_to_end(win)
    assert e2e["output_tok_per_s"] == pytest.approx(40.0)  # all tokens of the window / window
    # ttft: 0.1..0.9 s for the nine, 61 s (the wait when the run gave up) for the tenth
    assert e2e["ttft_p90_ms"] == pytest.approx(900.0)
    assert e2e["ttft_p50_ms"] == pytest.approx(500.0)  # the fifth of ten
    recs[0]["first"] = None
    recs[0]["ok"] = False
    recs[0]["done"] = None
    e2e = serve.end_to_end(win)
    assert e2e["ttft_p90_ms"] == pytest.approx(61000.0)  # two missing: p90 is a missing one
    assert e2e["ttft_p50_ms"] == pytest.approx(600.0)  # 0.2..0.9 s, then the two missing
    # tpot: (done - first) / (tokens - 1) for the finished, the wait for the missing
    # the eight finished: (2 - 0.1 (i + 1)) / 10 for i = 1..8; p90 of eight is the largest
    assert serve.end_to_end({**win, "records": recs[1:9]})["tpot_p90_ms"] == pytest.approx(
        1e3 * (2.0 - 0.2) / 10)


def test_serving_queue():
    recs = [_rec(i, i + 0.5, i + 3.0, 2) for i in range(10)]
    for r in recs:
        r["admitted"] = r["due"] + 2.0
    assert serve.waiting(recs, 4.5) == 2  # due at 3 and 4, admitted at 5 and 6
    assert serve.waiting(recs, 100) == 0
    recs[-1]["admitted"] = None
    assert serve.waiting(recs, 100) == 1


def test_queue_wait_reader():
    read = common.load_module("metrics", "queue_wait_ms.serve").read
    recs = [_rec(i, i + 0.5, i + 3.0, 2) for i in range(4)]
    for j, r in enumerate(recs):
        r["admitted"] = r["due"] + 0.1 * j
    recs.append({**_rec(4.0, None, None, 2, ok=False), "admitted": None})  # never admitted
    assert read({"window": {"records": recs}}) == pytest.approx(1e3 * 0.6 / 4)
    assert read({"window": {"records": recs[4:]}}) is None
    # a traced run reads the requests due before the profiler started
    assert read({"window": {"records": recs}, "trace_from": 2.0}) == pytest.approx(1e3 * 0.1 / 2)
    assert read({"window": {"records": recs}, "trace_from": None}) == pytest.approx(1e3 * 0.6 / 4)


def test_ttft_reader_matches_the_end_to_end_median():
    read = common.load_module("metrics", "ttft_p50_ms.serve").read
    recs = [_rec(i, i + 0.1 * (i + 1), i + 3.0, 2) for i in range(5)]
    recs.append(_rec(5.0, None, None, 2, ok=False))  # no first token: waited to the end
    win = {"records": recs, "end": 9.0, "emitted": 0, "seconds": 10.0}
    assert read({"window": win}) == pytest.approx(serve.end_to_end(win)["ttft_p50_ms"])
    assert read({"window": win}) == pytest.approx(300.0)
    assert read({"window": win, "trace_from": 2.5}) == pytest.approx(200.0)  # due at 0, 1, 2
    assert read({"window": win, "trace_from": 0.0}) is None


def test_step_reader_reads_the_steps_before_the_trace():
    read = common.load_module("metrics", "step_ms.serve").read
    steps = [(0.0, 0.4), (0.5, 1.0), (1.0, 20.0)]
    assert read({"window": {"steps": steps}}) == pytest.approx(1e3 * 19.9 / 3)
    assert read({"window": {"steps": steps}, "trace_from": 1.0}) == pytest.approx(1e3 * 0.45)
    assert read({"window": {"steps": steps}, "trace_from": 0.1}) is None


def test_serving_mfu_counts_the_work_over_the_steps():
    """Every served request's prefill and decoded tokens over the summed
    spans of the dispatching steps, not over the window: a run with the
    same work in half the step time reads twice as high."""
    read = common.load_module("metrics", "mfu.serve").read
    model = {"n_layers": 24, "d_model": 2048, "n_heads": 16, "n_kv_heads": 8,
             "head_dim": 128, "d_ff": 8192, "vocab_size": 92544}
    a, b = _rec(0.0, 1.0, 2.0, 3), _rec(1.0, 2.0, 3.0, 1)
    b["prompt"] = np.zeros(10, np.int32)
    idle = {**_rec(2.0, None, None, 0, ok=False), "tokens": []}
    work = (gqa.prefill_ops(model, 4) + gqa.decode_ops(model, 5) + gqa.decode_ops(model, 6)
            + gqa.prefill_ops(model, 10))
    peaks = common.peaks_for("TPU v5 lite")
    ctx = {"model": model, "config": {"reference": "dense_gqa"}, "peaks": peaks,
           "window": {"records": [a, b, idle], "steps": [(0.0, 0.5), (2.0, 2.25)],
                      "seconds": 51.0}}
    assert read(ctx) == pytest.approx(100.0 * work / 0.75 / 197e12)
    ctx["window"]["steps"] = [(0.0, 0.25), (2.0, 2.125)]
    assert read(ctx) == pytest.approx(200.0 * work / 0.75 / 197e12)
    ctx["window"]["steps"] = []
    assert read(ctx) is None


class _Plan:
    def __init__(self, n):
        self.reports = {"m": type("R", (), {"n_weights": n})()}
        self.deployed = {}


def test_planner_window_ends_at_the_last_layer_begun():
    gen = common.load_module("traffic", "layer_stream")
    model = {"n_layers": 2, "d_model": 128, "d_ff": 256, "head_dim": 32, "n_heads": 4,
             "n_kv_heads": 2}
    mix = {"drift": 0.0}

    gen.layer_params(1, model, 0.0, 0, 0)  # compile the generator outside the window

    def plan_layer(params):
        time.sleep(0.3)
        return _Plan(1000)

    recs = plan.run_window(gen, model, mix, 1, 1.0, plan_layer)
    # every layer began inside the window (each begins where the one before
    # ended), the last one ran on past its close, and none began after it
    assert len(recs) >= 2
    assert recs[-2]["end"] < 1.0 <= recs[-1]["end"]
    assert [(r["ckpt"], r["layer"]) for r in recs[:3]] == [(0, 0), (0, 1), (1, 0)][:len(recs)]


@pytest.mark.parametrize("m,k,n,ops,nbytes", [
    (8, 2048, 2048, 67_108_864, 5_865_472),
    (256, 8192, 2048, 8_589_934_592, 29_360_128),
])
def test_cim_matmul_counts(m, k, n, ops, nbytes):
    assert cim.ops_bytes(m, k, n, 10) == (ops, nbytes)


@pytest.mark.parametrize("t,w,ops,nbytes", [
    (4096, 160, 1_310_720, 1_327_104),
    (1000, 1280, 2_560_000, 2_564_000),
])
def test_hamming_counts(t, w, ops, nbytes):
    assert ham.ops_bytes(t, w) == (ops, nbytes)


def test_kernel_shapes_read_from_hlo_text():
    text = ("%cim_matmul_packed_kernel.3 = f32[8,2048]{1,0} custom-call(bf16[8,2048]{1,0} %x, "
            "u8[10,256,2048]{2,1,0} %p, u8[256,2048]{1,0} %s), custom_call_target=\"tpu_custom_call\"")
    assert cim.parse_call(text) == (8, 2048, 2048, 10, 2)
    assert cim.parse_call("fusion.1 = f32[8] add(f32[8] a, f32[8] b)") is None
    text = "%hamming = s32[4096]{0} custom-call(u8[4096,160]{1,0} %a, u8[4096,160]{1,0} %b)"
    assert ham.parse_call(text) == (4096, 160)
    peaks = common.peaks_for("TPU v5 lite")
    t, bound = cim.least_seconds(8, 2048, 2048, 10, 2, peaks)
    assert bound == "memory" and t == pytest.approx(5_865_472 / 819e9)
    t, bound = cim.least_seconds(256, 8192, 2048, 10, 2, peaks)
    assert bound == "compute" and t == pytest.approx(8_589_934_592 / 197e12)


def test_model_operation_counts():
    m = {"n_layers": 24, "d_model": 2048, "n_heads": 16, "n_kv_heads": 8, "head_dim": 128,
         "d_ff": 8192, "vocab_size": 92544}
    assert gqa.layer_ops_per_token(m) == 3_019_898_880
    assert gqa.head_ops(m) == 379_060_224
    assert gqa.attention_ops(m, 100) == 4 * 24 * 16 * 128 * 100
    assert gqa.decode_ops(m, 10) == 3_019_898_880 + 379_060_224 + 4 * 24 * 16 * 128 * 10
    assert gqa.prefill_ops(m, 3) == 3 * 3_019_898_880 + 379_060_224 + 4 * 24 * 16 * 128 * 6


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        common.peaks_for("cpu")
