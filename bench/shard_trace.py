"""One program run on several chips at once, call by call, from a trace.

A sharded call launches one SPMD program on every chip of the mesh; each
chip's plane ``/device:TPU:<i>`` then holds one module event for it
(``jit_sharded_scan(...)``).  The events of one call overlap in time: the
all-gather of the merge holds every chip until the slowest arrives.  So the
i-th call's events are matched by overlap with the first plane's, and a
call missing from any plane (cut by the window's edge) is left out.
"""

from __future__ import annotations

import bisect
import re

from bench import trace_reduce


def _events(trace: trace_reduce.Trace, plane: str, program: str, lo: float, hi: float):
    pat = re.compile(rf"(^|[^A-Za-z0-9]|jit_){re.escape(program)}([^A-Za-z0-9_]|$)")
    return sorted((e for e in trace.modules.get(plane, [])
                   if e.start >= lo and e.end <= hi and pat.search(e.name)),
                  key=lambda e: e.start)


def _overlapping(events, starts, e):
    """The event of ``events`` (sorted by start) that overlaps ``e`` most."""
    k = bisect.bisect_right(starts, e.end)
    best, most = None, 0.0
    for c in events[max(0, k - 3):k]:
        ov = min(c.end, e.end) - max(c.start, e.start)
        if ov > most:
            best, most = c, ov
    return best


def program_calls(trace: trace_reduce.Trace, program: str, lo: float,
                  hi: float) -> list[list[float]]:
    """Seconds of each call of ``program`` inside [lo, hi] on each device
    plane that ran it: one list per call, one entry per plane."""
    planes = sorted(p for p in trace.modules if _events(trace, p, program, lo, hi))
    if not planes:
        return []
    per = {p: _events(trace, p, program, lo, hi) for p in planes}
    starts = {p: [e.start for e in per[p]] for p in planes}
    calls = []
    for e in per[planes[0]]:
        got = [e] + [_overlapping(per[p], starts[p], e) for p in planes[1:]]
        if all(g is not None for g in got):
            calls.append([(g.end - g.start) / 1e9 for g in got])
    return calls
