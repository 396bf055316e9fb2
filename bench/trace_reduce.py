"""Reduce a JAX profiler trace to the benchmark's device numbers.

``jax.profiler.ProfileData.from_file`` reads the ``.xplane.pb`` that a traced
run writes.  On a TPU each chip is a plane ``/device:TPU:<i>``; its line
``XLA Ops`` holds one event per executed operation and ``XLA Modules`` one
per executed program (``jit_<function>(...)``).  The harness's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) sit on the host plane's
thread lines, on the same clock.  From these this module computes:

- busy time: the union of a device's operation intervals inside the traced
  window, averaged over the devices used; idle share = 1 - busy / window;
- the device time of named programs (for a roofline share);
- the operations that took most device time;
- the idle gaps, each charged to the innermost ``bench.*`` host span open at
  its midpoint (what the host was doing while the device waited).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

TPU_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float    # ns


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]      # device plane -> operations
    modules: dict[str, list[Event]]  # device plane -> executed programs
    spans: list[Event]               # bench.* host spans


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        if e.duration_ns > 0 and not e.name.startswith("end: "):
            out.append(Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return out


def load(log_dir: str, device_plane: str = TPU_PLANE, ops_line: str = OPS_LINE,
         modules_line: str = MODULES_LINE) -> Trace:
    """Device planes are those whose name starts with ``device_plane``; their
    operations are on lines whose name starts with ``ops_line``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(log_dir))
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        is_dev = plane.name.startswith(device_plane)
        for line in plane.lines:
            if is_dev and line.name.startswith(ops_line):
                ops[plane.name].extend(_events(line))
            elif is_dev and line.name.startswith(modules_line):
                modules[plane.name].extend(_events(line))
            if plane.name.startswith("/host:"):  # many runtime events: filter first
                spans.extend(Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                             for e in line.events
                             if e.name.startswith(HOST_SPAN_PREFIX) and e.duration_ns > 0)
    return Trace(dict(ops), dict(modules), spans)


def window(trace: Trace) -> tuple[float, float]:
    """(start, end) ns of the harness's window span."""
    w = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not w:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return w[0].start, w[0].end


def union(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged busy intervals of ``events`` clipped to [lo, hi]."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi)
    merged: list[list[float]] = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Union of operation intervals in [lo, hi], averaged over device planes
    that ran anything (0 where none did)."""
    per = [sum(e - s for s, e in union(evs, lo, hi)) for evs in trace.ops.values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) / 1e9 if per else 0.0


def named_times(events: list[Event], name: str, lo: float, hi: float) -> list[float]:
    """Durations (s) of events inside [lo, hi] whose name holds ``name`` as a
    word (``jit_scan_search(12)`` holds ``scan_search``)."""
    pat = re.compile(rf"(^|[^A-Za-z0-9]|jit_){re.escape(name)}([^A-Za-z0-9_]|$)")
    return [(e.end - e.start) / 1e9 for e in events
            if e.start >= lo and e.end <= hi and pat.search(e.name)]


def op_name(name: str) -> str:
    """The operation's own name: a TPU trace names an op by its HLO text
    (``%sort.18 = (bf16[...]) sort(...)``); keep ``sort.18``."""
    head = name.split(" = ", 1)[0] if " = " in name else name
    return head.lstrip("%").strip()


def self_times(events: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Seconds per operation inside [lo, hi], each event less the events
    nested in it (a ``while`` op holds its body's ops on the same line)."""
    tot: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [event, seconds of children]

    def close(until: float):  # pop every event that ends before ``until``
        while stack and stack[-1][0].end < until:
            e, child = stack.pop()
            own = (min(e.end, hi) - max(e.start, lo)) / 1e9
            tot[op_name(e.name)] += own - child
            if stack:
                stack[-1][1] += own

    for e in sorted((e for e in events if e.end > lo and e.start < hi),
                    key=lambda e: (e.start, -e.end)):
        close(e.end)  # what ends before e does not hold it
        stack.append([e, 0.0])
    close(float("inf"))
    return tot


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list[list]:
    """[[operation, seconds], ...] by self time on the device in [lo, hi],
    summed over devices."""
    tot: dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        for k, v in self_times(evs, lo, hi).items():
            tot[k] += v
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list[list]:
    """[[host span, seconds], ...]: idle time of the first device plane in
    [lo, hi], each gap charged to the innermost ``bench.*`` span (other than
    the window) open at its midpoint, or ``host.other``."""
    if not trace.ops:
        return []
    busy = union(next(iter(trace.ops.values())), lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    spans = sorted((s for s in trace.spans if s.name != WINDOW_SPAN),
                   key=lambda s: s.start)
    tot: dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        label = "host.other"
        for s in spans:
            if s.start > mid:
                break
            if s.end > mid:
                label = s.name  # later starts are nested deeper
        tot[label] += (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: Trace) -> dict:
    """The numbers a traced run reports: window, busy and idle, breakdown."""
    lo, hi = window(trace)
    busy = busy_seconds(trace, lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "idle_share": 1.0 - busy / ((hi - lo) / 1e9) if hi > lo else None,
        "device_ops": top_ops(trace, lo, hi),
        "idle_gaps": idle_gaps(trace, lo, hi),
    }


def reduce_dir(log_dir: str, **load_kw) -> dict:
    """``reduce`` of the trace under ``log_dir``, with the ``Trace`` itself
    under ``trace`` for readers that need named programs."""
    trace = load(log_dir, **load_kw)
    out = reduce(trace)
    out["trace"] = trace
    return out
