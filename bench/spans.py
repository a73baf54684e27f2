"""The program's own spans and scopes, from the trace ``bench/trace.py`` reads.

``trace.load`` keeps the harness's ``bench.*`` host spans and the device ops
by name.  The program marks its own work too: host spans named ``engine.*``,
``plan.*`` and ``pool.*`` (``jax.profiler.TraceAnnotation``; its keyword
arguments, host integers such as ``rows`` or ``n_weights``, land in the
trace as event stats), and ``jax.named_scope`` names inside its jitted
programs.  On a TPU a device op's name stack reaches the trace as the
``tf_op`` stat of the op's metadata (``jit(decode_loop)/kv_gather/...``),
and its program as the ``program_id`` stat, which the ``XLA Modules`` line
names (``jit_decode_loop(<id>)``).  ``jax.profiler.ProfileData`` gives an
event's own stats but not its metadata's, so ``load`` reads the
``.xplane.pb`` file as the protobuf it is.  It is the file ``trace.load``
reads, on the same clock, so every span, every op and the ``bench.window``
line up:

    {"spans": [[name, start_ns, end_ns, thread, {arg: value}], ...],
     "main": thread of the ``bench.window`` span,
     "ops": {"<plane>": [[name, start_ns, dur_ns, scope, module], ...]},
     "modules": {"<plane>": [[module, start_ns, dur_ns], ...]}}

``thread`` numbers the host lines of the trace (one per thread); ``scope`` is
the op's name stack ("" where the trace holds none) and ``module`` its
jitted program (``jit_decode_loop``).  XLA's own copies carry no name stack,
or their loop's: a copy takes the name stack of the value it copies, found
in the program's HLO, which the trace keeps on its ``/host:metadata`` plane
(``_copy_scopes``).  ``get`` reads the trace once per run and keeps it in
``ctx``; the window is ``ctx["trace"]["window"]``.
Everything below ``get`` is pure Python over that dict, checked on small
synthetic traces (``tests/bench``).

Idle attribution goes to the innermost span open on the thread that opened
the window: spans on other threads (the planner's compile workers) are host
work, but never the cover of an idle stretch.
"""
from __future__ import annotations

import functools
import glob
import os
import re

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from bench import trace as T

PROGRAM = ("engine.", "plan.", "pool.")
KEPT = PROGRAM + ("bench.",)
NO_SPAN = "no span"

# The fields of the profiler's ``XSpace`` protobuf (``tsl/profiler/protobuf/
# xplane.proto``) that ``load`` reads, by their numbers there: (name, number,
# type, label, message type or oneof).
_F = descriptor_pb2.FieldDescriptorProto
_ONE, _MANY = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED
_INT, _UINT, _DBL, _STR, _BYTES, _MSG = (_F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_DOUBLE,
                                         _F.TYPE_STRING, _F.TYPE_BYTES, _F.TYPE_MESSAGE)
_XPLANE = {
    "XSpace": [("planes", 1, _MSG, _MANY, "XPlane")],
    "XPlane": [("name", 2, _STR, _ONE, None), ("lines", 3, _MSG, _MANY, "XLine"),
               ("event_metadata", 4, _MSG, _MANY, "EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, _MANY, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, _INT, _ONE, None),
                           ("value", 2, _MSG, _ONE, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _INT, _ONE, None),
                          ("value", 2, _MSG, _ONE, "XStatMetadata")],
    "XLine": [("name", 2, _STR, _ONE, None), ("timestamp_ns", 3, _INT, _ONE, None),
              ("events", 4, _MSG, _MANY, "XEvent")],
    "XEvent": [("metadata_id", 1, _INT, _ONE, None), ("offset_ps", 2, _INT, _ONE, None),
               ("duration_ps", 3, _INT, _ONE, None), ("stats", 4, _MSG, _MANY, "XStat")],
    "XStat": [("metadata_id", 1, _INT, _ONE, None), ("double_value", 2, _DBL, _ONE, "value"),
              ("uint64_value", 3, _UINT, _ONE, "value"), ("int64_value", 4, _INT, _ONE, "value"),
              ("str_value", 5, _STR, _ONE, "value"), ("bytes_value", 6, _BYTES, _ONE, "value"),
              ("ref_value", 7, _UINT, _ONE, "value")],
    "XEventMetadata": [("name", 2, _STR, _ONE, None), ("display_name", 4, _STR, _ONE, None),
                       ("stats", 5, _MSG, _MANY, "XStat")],
    "XStatMetadata": [("name", 2, _STR, _ONE, None)],
    # a program's HLO, the ``Hlo Proto`` stat of the metadata plane
    # (``xla/service/hlo.proto``)
    "HloProto": [("hlo_module", 1, _MSG, _ONE, "HloModuleProto")],
    "HloModuleProto": [("computations", 3, _MSG, _MANY, "HloComputationProto")],
    "HloComputationProto": [("instructions", 2, _MSG, _MANY, "HloInstructionProto"),
                            ("id", 5, _INT, _ONE, None)],
    "HloInstructionProto": [("name", 1, _STR, _ONE, None), ("opcode", 2, _STR, _ONE, None),
                            ("metadata", 7, _MSG, _ONE, "OpMetadata"),
                            ("parameter_number", 9, _INT, _ONE, None),
                            ("tuple_index", 13, _INT, _ONE, None), ("id", 35, _INT, _ONE, None),
                            ("operand_ids", 36, _INT, _MANY, None),
                            ("called_computation_ids", 38, _INT, _MANY, None)],
    "OpMetadata": [("op_name", 2, _STR, _ONE, None)],
}


@functools.cache
def _message(name: str):
    """A parser class for the message ``name`` of ``_XPLANE``."""
    return message_factory.GetMessageClass(_pool().FindMessageTypeByName(f"bench_xplane.{name}"))


@functools.cache
def _pool():
    """The messages of ``_XPLANE`` as one protobuf file."""
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane",
                                            syntax="proto3")
    for msg, fields in _XPLANE.items():
        m = fd.message_type.add(name=msg)
        for name, number, typ, label, of in fields:
            field = m.field.add(name=name, number=number, type=typ, label=label)
            if typ == _MSG:
                field.type_name = f".bench_xplane.{of}"
            elif of:  # a member of the oneof ``of``
                if not m.oneof_decl:
                    m.oneof_decl.add(name=of)
                field.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return pool


def _stats(stats, names: dict) -> dict:
    """XStats -> {name: value}; a string may be held or referred to by id."""
    out = {}
    for st in stats:
        field = st.WhichOneof("value")
        if field == "ref_value":
            out[names.get(st.metadata_id)] = names.get(st.ref_value, "")
        elif field:
            out[names.get(st.metadata_id)] = getattr(st, field)
    return out


def _module(name: str) -> str:
    """``jit_decode_loop(123)`` -> ``jit_decode_loop``."""
    return re.sub(r"\(\d+\)$", "", name)


COPIES = ("copy", "copy-start", "copy-done")


def _copy_scopes(raw: bytes) -> dict[str, str]:
    """{copy: name stack of the value it copies} over one program's HLO
    (``HloProto``).  XLA adds its copies (layouts, loop carries) with no
    name stack or the enclosing loop's; the value's is that of the op that
    made it, followed back through bitcasts, copies, tuples and the carries
    of loops and calls."""
    mod = _message("HloProto").FromString(raw).hlo_module
    ins = {i.id: i for c in mod.computations for i in c.instructions}
    comp = {i.id: c.id for c in mod.computations for i in c.instructions}
    caller = {c: i for i in ins.values() if i.opcode in ("while", "call")
              for c in i.called_computation_ids}

    def origin(i):
        path = []  # tuple indices still to take, the next last
        for _ in range(256):
            if i.opcode in ("bitcast", *COPIES):
                i = ins[i.operand_ids[0]]
            elif i.opcode == "get-tuple-element":
                path.append(i.tuple_index)
                i = ins[i.operand_ids[0]]
            elif i.opcode == "tuple" and path:
                i = ins[i.operand_ids[path.pop()]]
            elif i.opcode == "while":  # a loop carries what entered it
                i = ins[i.operand_ids[0]]
            elif i.opcode == "parameter" and comp[i.id] in caller:
                call = caller[comp[i.id]]
                i = ins[call.operand_ids[0 if call.opcode == "while" else i.parameter_number]]
            else:
                break
        return i.metadata.op_name

    return {i.name: origin(i) for i in ins.values() if i.opcode in COPIES}


def load(log_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``log_dir`` (the file ``trace.load`` reads)."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    xs = _message("XSpace")()
    with open(files[-1], "rb") as f:
        xs.ParseFromString(f.read())
    spans, ops, modules, main, thread = [], {}, {}, None, 0
    hlo, copies = {}, {}  # program id -> its HLO; -> _copy_scopes of it
    for plane in xs.planes:
        if plane.name == "/host:metadata":
            for e in plane.event_metadata:
                for st in e.value.stats:
                    if st.WhichOneof("value") == "bytes_value":
                        hlo[e.value.name.rpartition("(")[2].rstrip(")")] = st.bytes_value
    for plane in xs.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            programs, plane_mods = {}, modules.setdefault(plane.name, [])
            ln = lines.get("XLA Modules")
            for ev in ln.events if ln else ():
                name = meta[ev.metadata_id].name
                programs[name.rpartition("(")[2].rstrip(")")] = _module(name)
                plane_mods.append([_module(name), ln.timestamp_ns + ev.offset_ps // 1000,
                                   ev.duration_ps // 1000])
            seen, plane_ops = {}, ops.setdefault(plane.name, [])
            ln = lines.get("XLA Ops")
            for ev in ln.events if ln else ():
                op = seen.get(ev.metadata_id)
                if op is None:
                    md = meta[ev.metadata_id]
                    st = _stats(md.stats, names)
                    name = md.display_name or md.name.partition(" = ")[0].lstrip("%")
                    scope, pid = str(st.get("tf_op", "")).rstrip(":"), str(st.get("program_id", ""))
                    if pid in hlo:
                        if pid not in copies:
                            copies[pid] = _copy_scopes(hlo[pid])
                        scope = copies[pid].get(name) or scope
                    op = seen[ev.metadata_id] = (name, scope, programs.get(pid, ""))
                plane_ops.append([op[0], ln.timestamp_ns + ev.offset_ps // 1000,
                                  ev.duration_ps // 1000, op[1], op[2]])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    name = meta[ev.metadata_id].name
                    if name.startswith(KEPT):
                        s = ln.timestamp_ns + ev.offset_ps // 1000
                        args = {k: v for k, v in _stats(ev.stats, names).items()
                                if isinstance(v, (int, float))}
                        spans.append([name, s, s + ev.duration_ps // 1000, thread, args])
                        if name == T.WINDOW_SPAN:
                            main = thread
                thread += 1
    spans.sort(key=lambda s: (s[1], -s[2]))
    for rows in (*ops.values(), *modules.values()):
        rows.sort(key=lambda r: r[1])
    return {"spans": spans, "main": main, "ops": ops, "modules": modules}


def get(ctx: dict) -> dict | None:
    """The run's program spans and device ops, read once and kept in ``ctx``;
    None without a trace."""
    if "spans" not in ctx:
        ctx["spans"] = None
        if ctx.get("trace") and ctx.get("trace_dir"):
            ctx["spans"] = load(ctx["trace_dir"])
    return ctx["spans"]


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def program(sp: dict) -> list:
    """Spans the program opened (``engine.*``, ``plan.*``, ``pool.*``)."""
    return [s for s in sp["spans"] if s[0].startswith(PROGRAM)]


def named(sp: dict, *names: str) -> list:
    """Spans of these names on the window's thread."""
    return [s for s in sp["spans"] if s[0] in names and s[3] == sp["main"]]


def inside(sp: dict, outer: list) -> list:
    """Spans on ``outer``'s thread that lie within it (``outer`` excluded)."""
    return [s for s in sp["spans"] if s[3] == outer[3] and s is not outer
            and outer[1] <= s[1] and s[2] <= outer[2]]


def self_ns(outer: list, children: list) -> int:
    """``outer``'s time less the time its children cover."""
    cover = T.union((max(a, outer[1]), min(b, outer[2])) for _, a, b, *_ in children)
    return (outer[2] - outer[1]) - sum(b - a for a, b in cover if b > a)


def share_inside(span: list, lo: int, hi: int) -> float:
    """The share of a span that lies inside [lo, hi)."""
    d = span[2] - span[1]
    if d <= 0:
        return 1.0 if lo <= span[1] < hi else 0.0
    return max(0, min(span[2], hi) - max(span[1], lo)) / d


def innermost(sp: dict, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """[lo, hi) cut into stretches, each with the innermost span open on the
    window's thread through it (``NO_SPAN`` where none is; the window's own
    span does not count)."""
    spans = [s for s in sp["spans"] if s[3] == sp["main"] and s[0] != T.WINDOW_SPAN
             and s[2] > lo and s[1] < hi]
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for s in spans for t in s[1:3]})
    out, k, stack = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        # spans are sorted by start (longest first on a tie): push every span
        # begun by a, pop every span ended by a; the top is the innermost
        while k < len(spans) and spans[k][1] <= a:
            stack.append(spans[k])
            k += 1
        stack = [s for s in stack if s[2] > a]
        name = max(stack, key=lambda s: (s[1], -s[2]))[0] if stack else NO_SPAN
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_stretches(tr: dict) -> list[tuple[int, int]]:
    """Stretches of the window in which no op ran on the first device traced:
    the stretches ``trace.idle_gaps`` splits by ``bench.*`` span."""
    lo, hi = tr["window"]
    planes = [ops for ops in tr["device"].values() if ops]
    if not planes:
        return []
    busy = T.union((a, b) for _, a, b, _ in T._clip(planes[0], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def idle_by_span(sp: dict, tr: dict) -> dict[str, float]:
    """Device idle seconds in the window by the innermost span on the
    window's thread (``NO_SPAN`` where none is open)."""
    lo, hi = tr["window"]
    cover = innermost(sp, lo, hi)
    out: dict[str, float] = {}
    j = 0
    for g0, g1 in idle_stretches(tr):
        while j < len(cover) and cover[j][1] <= g0:
            j += 1
        i = j
        while i < len(cover) and cover[i][0] < g1:
            a, b = max(g0, cover[i][0]), min(g1, cover[i][1])
            if b > a:
                out[cover[i][2]] = out.get(cover[i][2], 0.0) + (b - a) / 1e9
            i += 1
    return out


def is_container(name: str) -> bool:
    return re.sub(r"(\.\d+)+$", "", name) in T.CONTAINERS


def device_seconds(sp: dict, lo: int, hi: int, pick) -> float | None:
    """Device seconds inside [lo, hi) of the ops ``pick(scopes, module, name)``
    takes, given the components of the op's name stack, its module and its
    name.
    Container ops (``while`` ...) are left out: their time is their body
    ops'.  None when ``pick`` takes no op of the whole trace, so that a
    program without these names reads nothing rather than 0."""
    found, tot = False, 0
    for ops in sp["ops"].values():
        for name, start, dur, scope, module in ops:
            if not pick(scope.split("/") if scope else [], module, name):
                continue
            found = True
            a, b = max(start, lo), min(start + dur, hi)
            if b > a and not is_container(name):
                tot += b - a
    return tot / 1e9 if found else None


def module_runs(sp: dict, lo: int, hi: int, *names: str) -> float:
    """How many runs of the modules whose names hold any of ``names`` lie in
    [lo, hi), each weighted by the share of it inside."""
    return sum(share_inside([m, s, s + d], lo, hi)
               for mods in sp["modules"].values() for m, s, d in mods
               if any(n in m for n in names))


def weighted(sp: dict, name: str, lo: int, hi: int, arg: str | None = None) -> float:
    """The spans ``name`` on the window's thread in [lo, hi), each weighted by
    its share inside; with ``arg``, the sum of that counter so weighted."""
    return sum(share_inside(s, lo, hi) * (s[4].get(arg, 0) if arg else 1)
               for s in named(sp, name))


def tensors_in(sp: dict, lo: int, hi: int) -> float:
    """``plan.tensor`` spans in [lo, hi), each weighted by its share inside."""
    return weighted(sp, "plan.tensor", lo, hi)
