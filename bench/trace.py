"""Profiler traces: capture a window, read it, reduce it to device numbers.

``Tracer`` wraps ``jax.profiler`` around part of a run and marks the
traced interval with a host span ``bench.window``; the harness marks its
own host work with further ``bench.*`` spans, which land on the same clock
as the device's operations.  ``load`` turns the ``.xplane.pb`` file into a
plain dict (the format of the test fixture):

    {"device": {"<plane>": [[op name, start_ns, dur_ns, detail], ...]},
     "host": [[span name, start_ns, dur_ns], ...],
     "window": [start_ns, end_ns]}

``detail`` is the op's HLO text where the trace carries it (operand shapes).
Everything below ``load`` is pure Python over that dict.
"""
from __future__ import annotations

import glob
import os
import re
from contextlib import nullcontext

WINDOW_SPAN = "bench.window"


class Tracer:
    """Start and stop a trace at two points of a loop.  Host tracing is kept
    (spans), the Python tracer is off (it would slow the host the benchmark
    measures)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.on = False
        self._span = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.on = True

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def span(self, name: str):
        """A host span while tracing, else nothing."""
        if not self.on:
            return nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def _op(text: str, seen: dict) -> tuple[str, str]:
    """(op name, detail) of a device event: on a TPU an op event is named by
    its HLO text, ``%name = shape op(operands), ...``; the detail (operand
    shapes) is kept for custom calls (the kernels) only."""
    got = seen.get(text)
    if got is None:
        head, _, rest = text.partition(" = ")
        name = head.lstrip("%") if rest else text
        detail = text if "custom-call(" in rest else ""
        got = seen[text] = (name, detail)
    return got


def load(log_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    device, host, seen = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = device[plane.name] = []
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    for ev in ln.events:
                        name, detail = _op(ev.name, seen)
                        ops.append([name, int(ev.start_ns), int(ev.duration_ns), detail])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace under {log_dir} has no {WINDOW_SPAN} span")
    w = max(win, key=lambda h: h[2])
    return {"device": device, "host": host, "window": [w[1], w[1] + w[2]]}


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def _clip(ops, lo, hi):
    for name, start, dur, *rest in ops:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b, (rest[0] if rest else "")


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window_s(tr: dict) -> float:
    lo, hi = tr["window"]
    return (hi - lo) / 1e9


def busy_s(tr: dict) -> float:
    """Seconds of the window in which some operation ran on a device,
    averaged over the devices traced."""
    lo, hi = tr["window"]
    planes = [ops for ops in tr["device"].values() if ops]
    if not planes:
        return 0.0
    tot = 0
    for ops in planes:
        tot += sum(b - a for a, b in union((a, b) for _, a, b, _ in _clip(ops, lo, hi)))
    return tot / len(planes) / 1e9


def op_seconds(tr: dict) -> dict[str, float]:
    """Device seconds per op name inside the window (summed over devices,
    numeric suffixes such as ``.12`` folded)."""
    lo, hi = tr["window"]
    out: dict[str, float] = {}
    for ops in tr["device"].values():
        for name, a, b, _ in _clip(ops, lo, hi):
            key = re.sub(r"(\.\d+)+$", "", name)
            out[key] = out.get(key, 0.0) + (b - a) / 1e9
    return out


def kernel_calls(tr: dict, pattern: str) -> list[tuple[float, str]]:
    """(seconds, detail) of every device op inside the window whose name
    contains ``pattern``."""
    lo, hi = tr["window"]
    return [((b - a) / 1e9, d) for ops in tr["device"].values()
            for name, a, b, d in _clip(ops, lo, hi) if pattern in name]


def idle_gaps(tr: dict) -> list[tuple[str, float]]:
    """Device idle time in the window, by the innermost ``bench.*`` host span
    (other than the window's own) that covers each idle stretch; stretches
    no span covers are ``host.other``.  Sorted, most idle first."""
    lo, hi = tr["window"]
    planes = [ops for ops in tr["device"].values() if ops]
    if not planes:
        return []
    busy = union((a, b) for _, a, b, _ in _clip(planes[0], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    spans = sorted((s, s + d, n) for n, s, d in tr["host"] if n != WINDOW_SPAN)
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        covered = 0
        for s0, s1, name in spans:
            a, b = max(g0, s0), min(g1, s1)
            if b > a:
                out[name] = out.get(name, 0.0) + (b - a) / 1e9
                covered += b - a
        rest = (g1 - g0) - covered
        if rest > 0:
            out["host.other"] = out.get("host.other", 0.0) + rest / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])


CONTAINERS = ("while", "conditional", "call")  # their time is their body ops' time


def breakdown(tr: dict) -> dict:
    ops = sorted(((n, s) for n, s in op_seconds(tr).items() if n not in CONTAINERS),
                 key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps(tr)[:10]]}
