"""The readers of the program's own spans and scopes (``bench/spans.py`` and
seven ``bench/metrics``), on two made-up windows of [0, 1000) ns
(``spans_synthetic.json``) whose every reading is counted by hand below."""
import json
import os

import pytest

from bench import common
from bench import spans as S
from bench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
SEVEN = ("host_ms.serve", "decode_pad_share.serve", "kv_view_ms.serve", "host_held.plan",
         "compile_prep.plan", "prep_ms.plan", "walk_ms.plan")


def _ctx(kind: str) -> dict:
    """A reader's ctx: the spans as ``spans.get`` keeps them, and the trace
    as ``trace.load`` returns it (the same ops, the ``bench.*`` spans)."""
    with open(os.path.join(HERE, "spans_synthetic.json")) as f:
        sp = json.load(f)[kind]
    tr = {"device": {p: [[n, s, d, ""] for n, s, d, *_ in ops] for p, ops in sp["ops"].items()},
          "host": [[n, s, e - s] for n, s, e, *_ in sp["spans"] if n.startswith("bench.")],
          "window": [0, 1000]}
    return {"trace": tr, "spans": sp}


def _read(name: str, ctx: dict):
    return common.load_module("metrics", name).read(ctx)


def test_idle_goes_to_the_innermost_span_of_the_window_thread():
    ctx = _ctx("plan")
    idle = S.idle_by_span(ctx["spans"], ctx["trace"])
    want = {  # ns of device idle under each innermost span (see the fixture)
        "bench.plan_layer": 50, "plan.deployment": 10, "plan.compile_prep": 140,
        "plan.tensor": 5 + 2 + 5 + 150 + 290, "plan.prep": 10 + 15, "pool.assign": 8,
        "pool.seam": 5, "pool.seam.readback": 1 + 2, "pool.walk": 10,
        "pool.walk.readback": 2, "pool.commit": 8, "pool.program": 2, "plan.dequant": 10,
        "plan.report": 2, "plan.deployed.readback": 18,
    }
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # every idle stretch is covered, and they add up to the device's idle share
    assert sum(idle.values()) == pytest.approx(T.window_s(ctx["trace"]) - T.busy_s(ctx["trace"]))
    assert _read("device_idle.plan", ctx) == pytest.approx(74.5)


def test_worker_thread_spans_never_cover_idle_time():
    ctx = _ctx("plan")
    sp = ctx["spans"]
    # without plan.compile_prep on the window's thread, its idle stretch
    # falls to plan.deployment, never to the compile workers' spans
    sp["spans"] = [s for s in sp["spans"] if s[0] != "plan.compile_prep"]
    idle = S.idle_by_span(sp, ctx["trace"])
    assert "plan.compile_prep.size" not in idle
    assert idle["plan.deployment"] == pytest.approx((10 + 140) * 1e-9)
    # and with no span at all on the window's thread, it is no span's
    sp["spans"] = [s for s in sp["spans"] if s[3] != sp["main"] or s[0] == T.WINDOW_SPAN]
    assert S.idle_by_span(sp, ctx["trace"]) == pytest.approx({S.NO_SPAN: 745e-9})


def test_innermost_cuts_and_clips_to_the_window():
    sp = {"main": 0, "spans": [["a", -50, 60, 0, {}], ["b", 10, 20, 0, {}],
                               ["c", 20, 40, 0, {}], ["w", 30, 90, 1, {}]]}
    assert S.innermost(sp, 0, 100) == [(0, 10, "a"), (10, 20, "b"), (20, 40, "c"),
                                       (40, 60, "a"), (60, 100, S.NO_SPAN)]


def test_self_time_of_a_step_without_its_readbacks():
    ctx = _ctx("serve")
    sp = ctx["spans"]
    (step,) = [s for s in S.named(sp, "engine.step") if s[1] == 100]
    kids = S.inside(sp, step)
    assert S.self_ns(step, kids) == 400 - (20 + 80 + 280)  # admit, prefill, decode
    assert S.self_ns(step, [k for k in kids if k[0].endswith(".readback")]) == 190
    # steps inside the window that dispatched: 190 and 300 - 220 ns; the step
    # without a dispatch and the two crossing the window's ends are left out
    assert _read("host_ms.serve", ctx) == pytest.approx(1e-6 * (190 + 80) / 2)


def test_host_time_of_a_step_split_by_phase(capsys):
    ctx = _ctx("serve")
    recs = common.load_module("metrics", "host_ms.serve").steps(ctx["spans"], 0, 1000)
    # own time, then admit, prepare, dispatch, commit and the rest, in ns
    assert [tuple(r[k] for k in ("own", "engine.admit", ".prepare", ".dispatch", ".commit",
                                 "other")) for r in recs] == [(190, 20, 40, 40, 30, 60),
                                                               (80, 10, 0, 20, 0, 50)]
    assert [(r["admitted"], r["live"], r["waiting"]) for r in recs] == [(1, 3, 2), (0, 3, 0)]
    _read("host_ms.serve", ctx)
    err = capsys.readouterr().err
    assert ("admit 0.000015, prepare 0.000020, dispatch 0.000030, commit 0.000015, "
            "other 0.000055; 1 admitted; a step began with 3.000 live and 1.000 waiting") in err


def test_dispatch_shapes_from_the_counters(capsys):
    ctx = _ctx("serve")
    sp = ctx["spans"]
    mod = common.load_module("metrics", "decode_pad_share.serve")
    assert mod.shapes(sp, 0, 1000, mod.DECODE) == {
        "n": 3, "rows": 9, "pad": 3, "pages": 12, "q": 18, "tokens": 0}
    assert mod.shapes(sp, 0, 1000, mod.PREFILL) == {
        "n": 1, "rows": 1, "pad": 0, "pages": 2, "q": 0, "tokens": 30}
    _read("decode_pad_share.serve", ctx)
    err = capsys.readouterr().err
    assert "1 prefill dispatches, pad share 0.000%, a dispatch 1.000 rows, 2.000 pages, " \
        "30.000 tokens" in err
    assert "3 decode dispatches, a dispatch 3.000 rows, 1.000 padded, 4.000 pages, " \
        "quantum 6.000" in err
    # a fused dispatch counts its decode sub-batch as a decode dispatch and
    # its prefill sub-batch as a prefill dispatch
    sp["spans"].append(["engine.fused", 10, 20, 0, {
        "rows": 2, "rows_padded": 2, "pages": 4, "q": 8, "prefill_rows": 1,
        "prefill_rows_padded": 1, "tokens": 16}])
    assert mod.shapes(sp, 0, 1000, mod.PREFILL) == {
        "n": 2, "rows": 2, "pad": 1, "pages": 6, "q": 8, "tokens": 46}
    assert _read("decode_pad_share.serve", ctx) == pytest.approx(100.0 * 5 / 16)
    assert "2 prefill dispatches, pad share 33.333%" in capsys.readouterr().err


def test_decode_pad_share():
    # decode spans begun in the window: 3 + 1 pad, 2 + 2 pad, 4 + 0 pad; the
    # one begun before the window (1 + 7 pad) is left out
    assert _read("decode_pad_share.serve", _ctx("serve")) == pytest.approx(100.0 * 3 / 12)


def test_kv_view_per_decode_dispatch_clipped_to_the_window():
    # kv ops of the decode program: 40 + 20 + 20 + 10 (clipped at 1000) ns;
    # the prefill's gather is another program; runs 1 + 1 + 0.2 (20 of 100 ns inside)
    ctx = _ctx("serve")
    sp = ctx["spans"]
    assert S.module_runs(sp, 0, 1000, "decode_loop") == pytest.approx(2.2)
    assert _read("kv_view_ms.serve", ctx) == pytest.approx(1e3 * 90e-9 / 2.2)


def test_kv_view_prints_the_decode_programs_other_copies(capsys):
    # copy.4 is the scatter's; copy.9, 10 ns under the decode loop, is not
    _read("kv_view_ms.serve", _ctx("serve"))
    assert f"other copies {1e3 * 10e-9 / 2.2:.6f}" in capsys.readouterr().err


def test_planner_host_share_and_compile_share():
    ctx = _ctx("plan")
    held = 10 + 140 + 452 + 25 + 8 + 5 + 10 + 8 + 2 + 10 + 2  # plan.*, pool.*, no read-back
    assert _read("host_held.plan", ctx) == pytest.approx(100.0 * held / 1000)
    assert _read("compile_prep.plan", ctx) == pytest.approx(14.0)


def test_compile_prep_per_call_and_per_size(capsys):
    _read("compile_prep.plan", _ctx("plan"))
    assert ("1 calls in the window, 2.000 sizes a call, 0.000140 ms a call, "
            "0.000070 ms a size") in capsys.readouterr().err


def test_counters_weighted_by_the_share_of_their_span_in_the_window(capsys):
    ctx = _ctx("plan")
    sp = ctx["spans"]
    share = 620 / 670  # of the second tensor
    weights = 1000 + 1024 * share
    sections = 8 + 8 * share
    assert S.weighted(sp, "plan.tensor", 0, 1000, "n_weights") == pytest.approx(weights)
    assert S.weighted(sp, "plan.tensor", 0, 1000, "sections") == pytest.approx(sections)
    assert S.weighted(sp, "pool.program", 0, 1000, "chains") == 4
    assert S.weighted(sp, "plan.tensor", 0, 1000) == S.tensors_in(sp, 0, 1000)
    for name in ("prep_ms.plan", "walk_ms.plan", "host_held.plan"):
        _read(name, ctx)
    err = capsys.readouterr().err
    assert f"{1e9 * 93e-9 / weights:.6f} ns a weight" in err
    assert f"{1e6 * 144e-9 / sections:.6f} us a section" in err
    # idle under pool.assign, seam, walk, commit and program: 8 + 5 + 10 + 8 + 2 ns
    assert f"pool host code {1e6 * 33e-9 / 4:.3f} us idle a chain, over 4.000 chains" in err


def test_device_time_per_tensor_weighted_by_its_share_in_the_window():
    ctx = _ctx("plan")
    sp = ctx["spans"]
    n = 1 + 620 / 670  # the second tensor runs to 1050, past the window's end
    assert S.tensors_in(sp, 0, 1000) == pytest.approx(n)
    # the prep program: 35 + 8 + 50 ns
    assert _read("prep_ms.plan", ctx) == pytest.approx(1e3 * 93e-9 / n)
    # intra 4, seam 2, walk 28 + 100 + 10 (clipped); the while op is its body's
    assert _read("walk_ms.plan", ctx) == pytest.approx(1e3 * 144e-9 / n)
    # a renamed walk function is still the walk, by its scope
    for ops in sp["ops"].values():
        for op in ops:
            op[4] = op[4].replace("_stuck_program_packed", "_walk")
    assert _read("walk_ms.plan", ctx) == pytest.approx(1e3 * 144e-9 / n)


def _xplane(path: str) -> None:
    """A two-plane trace in the form a TPU run writes: host spans with their
    arguments as event stats; device ops whose metadata holds the op's name
    stack (``tf_op``) and program (``program_id``), which the ``XLA
    Modules`` line names."""
    xs = S._message("XSpace")()

    def plane(name, events, stats):
        p = xs.planes.add(name=name)
        for i, s in enumerate(stats, 1):
            e = p.stat_metadata.add(key=i)
            e.value.name = s
        for i, (ev_name, md_stats) in enumerate(events, 1):
            e = p.event_metadata.add(key=i)
            e.value.name = ev_name
            for k, v in md_stats.items():
                st = e.value.stats.add(metadata_id=stats.index(k) + 1)
                setattr(st, "str_value" if isinstance(v, str) else "int64_value", v)
        return p

    host = plane("/host:CPU", [("bench.window", {}), ("engine.decode", {}), ("other", {})],
                 ["rows", "kind"])
    ln = host.lines.add(name="python3", timestamp_ns=1000)
    ln.events.add(metadata_id=1, offset_ps=0, duration_ps=900_000)
    ev = ln.events.add(metadata_id=2, offset_ps=100_000, duration_ps=500_000)
    ev.stats.add(metadata_id=1, int64_value=3)
    ev.stats.add(metadata_id=2, ref_value=2)  # a string held by reference: not an argument
    ln.events.add(metadata_id=3, offset_ps=0, duration_ps=1_000)
    host.lines.add(name="worker", timestamp_ns=0).events.add(metadata_id=2, duration_ps=5)
    dev = plane("/device:TPU:0", [
        ("jit_decode_loop(42)", {}),
        ("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
         {"tf_op": "jit(decode_loop)/kv_gather/jit(_take)/gather:", "program_id": 42}),
    ], ["tf_op", "program_id"])
    dev.lines.add(name="XLA Modules", timestamp_ns=1000).events.add(
        metadata_id=1, offset_ps=200_000, duration_ps=300_000)
    dev.lines.add(name="XLA Ops", timestamp_ns=1000).events.add(
        metadata_id=2, offset_ps=250_500, duration_ps=10_000)
    with open(path, "wb") as f:
        f.write(xs.SerializeToString())


def test_load_reads_spans_args_scopes_and_programs(tmp_path):
    _xplane(str(tmp_path / "t.xplane.pb"))
    sp = S.load(str(tmp_path))
    assert sp["main"] == 0
    assert sp["spans"] == [["engine.decode", 0, 0, 1, {}],  # by start: the worker's first
                           ["bench.window", 1000, 1900, 0, {}],
                           ["engine.decode", 1100, 1600, 0, {"rows": 3}]]
    assert sp["ops"] == {"/device:TPU:0": [
        ["fusion.7", 1250, 10, "jit(decode_loop)/kv_gather/jit(_take)/gather", "jit_decode_loop"]]}
    assert sp["modules"] == {"/device:TPU:0": [["jit_decode_loop", 1200, 300]]}
    assert S.get({"trace": None, "trace_dir": str(tmp_path)}) is None  # an untraced run
    ctx = {"trace": {"window": [1000, 1900]}, "trace_dir": str(tmp_path)}
    assert S.get(ctx) is S.get(ctx) == sp  # read once per run
    assert _read("kv_view_ms.serve", ctx) == pytest.approx(1e3 * 10e-9 / 1)


def _hlo() -> bytes:
    """The decode program's HLO as the trace keeps it: a gather under
    ``kv_gather`` whose view enters a loop; XLA's copies of the view, after
    the loop and inside its body, and a copy of an entry parameter."""
    h = S._message("HloProto")()
    entry, body = h.hlo_module.computations.add(id=1), h.hlo_module.computations.add(id=2)

    def add(comp, name, opcode, i, ops=(), scope="", **kw):
        ins = comp.instructions.add(name=name, opcode=opcode, id=i, operand_ids=ops, **kw)
        ins.metadata.op_name = scope

    add(entry, "p", "parameter", 1)
    add(entry, "fusion.7", "fusion", 2, [1], "jit(decode_loop)/kv_gather/jit(_take)/gather")
    add(entry, "bitcast.5", "bitcast", 3, [2])
    add(entry, "tuple.2", "tuple", 4, [3, 1])
    add(entry, "while.1", "while", 5, [4], "jit(decode_loop)/while", called_computation_ids=[2, 3])
    add(entry, "get-tuple-element.3", "get-tuple-element", 6, [5], "jit(decode_loop)/while",
        tuple_index=0)
    add(entry, "copy.8", "copy", 7, [6])
    add(entry, "copy.9", "copy", 8, [1], "jit(decode_loop)/while")
    add(body, "param.1", "parameter", 11)
    add(body, "get-tuple-element.4", "get-tuple-element", 12, [11], tuple_index=0)
    add(body, "copy.10", "copy", 13, [12], "jit(decode_loop)/while/body")
    return h.SerializeToString()


def test_load_gives_a_copy_the_scope_of_the_value_it_copies(tmp_path):
    _xplane(str(tmp_path / "t.xplane.pb"))
    path = tmp_path / "t.xplane.pb"
    xs = S._message("XSpace")()
    xs.ParseFromString(path.read_bytes())
    dev = xs.planes[1]
    ops = dev.lines[1]
    for i, (name, scope) in enumerate((("copy.8", ""), ("copy.9", "jit(decode_loop)/while"),
                                       ("copy.10", "jit(decode_loop)/while/body")), 3):
        e = dev.event_metadata.add(key=i)
        e.value.name = f"%{name} = bf16[8]{{0}} copy(bf16[8]{{0}} %x)"
        e.value.display_name = name
        e.value.stats.add(metadata_id=2, int64_value=42)
        if scope:
            e.value.stats.add(metadata_id=1, str_value=scope)
        ops.events.add(metadata_id=i, offset_ps=300_000 + 10_000 * i, duration_ps=5_000)
    meta = xs.planes.add(name="/host:metadata")
    meta.stat_metadata.add(key=1).value.name = "Hlo Proto"
    e = meta.event_metadata.add(key=42)
    e.value.name = "jit_decode_loop(42)"
    e.value.stats.add(metadata_id=1, bytes_value=_hlo())
    path.write_bytes(xs.SerializeToString())
    sp = S.load(str(tmp_path))
    gather = "jit(decode_loop)/kv_gather/jit(_take)/gather"
    assert [op[3] for op in sp["ops"]["/device:TPU:0"]] == [
        gather,  # fusion.7, by its own name stack
        gather,  # copy.8: a loop's result is what entered it, a bitcast of the gather
        "jit(decode_loop)/while",  # copy.9: an entry parameter has no name stack
        gather,  # copy.10: the body's parameter is the loop's carry
    ]
    ctx = {"trace": {"window": [1000, 1900]}, "spans": sp}
    assert _read("kv_view_ms.serve", ctx) == pytest.approx(1e3 * (10e-9 + 2 * 5e-9))


@pytest.mark.parametrize("name", SEVEN)
def test_a_program_without_spans_reads_nothing(name):
    """The parent of this benchmark's readers: only ``bench.*`` spans, ops with
    neither scope nor module.  Every reader returns None and raises nothing."""
    ctx = _ctx("plan" if name.endswith(".plan") else "serve")
    sp = ctx["spans"]
    sp["spans"] = [s for s in sp["spans"] if s[0].startswith("bench.")]
    sp["ops"] = {p: [o[:3] + ["", ""] for o in ops] for p, ops in sp["ops"].items()}
    sp["modules"] = {p: [] for p in sp["modules"]}
    assert _read(name, ctx) is None
    assert _read(name, {"trace": None}) is None  # an untraced run
