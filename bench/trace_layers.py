#!/usr/bin/env python3
"""Per-layer time from the program's own spans and scopes in a profiler trace.

The program names its layers in the trace (``docs/tracing.md``): host spans
``velo.<layer>.<what>`` written by ``repro.core.spans.span``, and
``jax.named_scope`` names inside its jitted programs (``velo.scan.stage1``,
``velo.scan.select`` and ``velo.scan.rerank`` in ``scan_search``).  From a
trace this module computes:

- the self time of each host span name: its time less what the spans nested
  in it on the same thread cover;
- the device's idle gaps, each charged to the innermost ``bench.*`` or
  ``velo.*`` span open at its midpoint (the rule of ``trace_reduce.idle_gaps``,
  in one sweep, so that tens of thousands of spans cost seconds, not hours);
- the device self time of each scope of a named program: each operation of
  the program goes to the innermost ``velo.*`` scope of its HLO ``op_name``.
  A TPU trace names an operation by its HLO instruction alone, so the scopes
  come from the compiled program's HLO text, where a fusion takes its
  root's scope.

``bench/trace_reduce.py`` stays as it is: the result line's numbers do not
move; its ``Trace`` gives the device operations used here.  Run as a
script, this runs one cell with the trace on, as ``bench/run.py --trace 1``
does, reads the spans before the trace is deleted, prints the layers on
standard error and, last on standard output, one JSON object with the
cell's result line under ``result`` and the layers under ``layers``:

  python bench/trace_layers.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import os
import re
import sys
import time
from collections import defaultdict

T_START = time.time()

if __name__ == "__main__":  # as bench/run.py sets itself up
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_REPO, "bench", ".cache", "jax")
    sys.path[:0] = [_REPO, os.path.join(_REPO, "src")]

from bench import trace_reduce  # noqa: E402

SPAN_PREFIXES = ("bench.", "velo.")
SCOPE = re.compile(r"velo\.[A-Za-z0-9_]+\.[A-Za-z0-9_]+")
OP_NAME = re.compile(r'op_name="([^"]*)"')

# the served path's layers: span names (or name prefixes) whose self time
# each one sums
SERVED_LAYERS = {
    "engine_sched": ("velo.engine.run", "velo.engine.flush"),
    "search_step": ("velo.search.step",),
    "cache_host": ("velo.cache.",),
    "dist_host": ("velo.dist.call",),
    "dist_wait": ("velo.dist.fetch",),
}


@dataclasses.dataclass
class Span:
    name: str
    start: float   # ns
    end: float     # ns
    thread: int    # index of the host line (thread) it ran on
    attrs: dict


def innermost_scope(op_name: str) -> str | None:
    """The last ``velo.<layer>.<what>`` component of an HLO op_name path
    (``jit(scan_search)/while/body/velo.scan.select/sort`` ->
    ``velo.scan.select``), or None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def load_spans(log_dir: str) -> list[Span]:
    """The ``bench.*`` and ``velo.*`` host spans of the trace under
    ``log_dir``, with their thread and attributes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_reduce.find_xplane(log_dir))
    spans: list[Span] = []
    lines = (ln for p in pd.planes if p.name.startswith("/host:") for ln in p.lines)
    for thread, line in enumerate(lines):
        spans.extend(Span(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                          thread, dict(e.stats))
                     for e in line.events
                     if e.name.startswith(SPAN_PREFIXES) and e.duration_ns > 0)
    return spans


# ------------------------------------------------------------- host spans


def span_self_times(spans: list[Span], lo: float, hi: float) -> dict[str, float]:
    """Seconds per span name inside [lo, hi]: each span less the spans nested
    in it on its own thread."""
    by_thread: dict[int, list[Span]] = defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(s)
    tot: dict[str, float] = defaultdict(float)
    for evs in by_thread.values():
        for k, v in trace_reduce.self_times(evs, lo, hi).items():
            tot[k] += v
    return dict(tot)


def layer_seconds(self_times: dict[str, float], names: tuple[str, ...]) -> float:
    """Sum of the self times of the span names equal to, or (for a name
    ending in ``.``) starting with, one of ``names``."""
    return sum(v for k, v in self_times.items()
               if any(k == n or (n.endswith(".") and k.startswith(n)) for n in names))


def span_counts(spans: list[Span], lo: float, hi: float) -> dict[str, int]:
    out: dict[str, int] = defaultdict(int)
    for s in spans:
        if s.start >= lo and s.end <= hi:
            out[s.name] += 1
    return dict(out)


# --------------------------------------------------------------- idle gaps


def device_gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] in which none of ``ops`` runs, in order."""
    gaps, prev = [], lo
    for s, e in trace_reduce.union(ops, lo, hi):
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    return gaps


def charge_gaps(gaps: list[tuple[float, float]], spans,
                skip: str = trace_reduce.WINDOW_SPAN) -> dict[str, float]:
    """Seconds of ``gaps`` (sorted, disjoint) per span: each gap goes to the
    span open at its midpoint that started last (the innermost; the latest in
    input order among equal starts), or to ``host.other``.  One sweep: spans
    enter a heap as the midpoints pass their start and leave it once a
    midpoint passes their end."""
    order = sorted(range(len(spans)), key=lambda i: spans[i].start)
    order = [i for i in order if spans[i].name != skip]
    heap: list[tuple[float, int, int]] = []  # (-start, -rank, index)
    tot: dict[str, float] = defaultdict(float)
    nxt = 0
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        while nxt < len(order) and spans[order[nxt]].start <= mid:
            i = order[nxt]
            heapq.heappush(heap, (-spans[i].start, -nxt, i))
            nxt += 1
        while heap and spans[heap[0][2]].end <= mid:
            heapq.heappop(heap)
        label = spans[heap[0][2]].name if heap else "host.other"
        tot[label] += (g1 - g0) / 1e9
    return dict(tot)


def idle_gaps(trace: trace_reduce.Trace, spans: list[Span], lo: float, hi: float,
              n: int = 10) -> list[list]:
    """[[span, seconds], ...]: the first device plane's idle time in [lo, hi]
    by the innermost of ``spans`` open during it."""
    if not trace.ops:
        return []
    gaps = device_gaps(next(iter(trace.ops.values())), lo, hi)
    tot = charge_gaps(gaps, spans)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------------------ device scopes


def scopes_from_hlo(text: str) -> dict[str, str]:
    """HLO instruction name -> innermost ``velo.*`` scope, from the text of a
    compiled module (``jitted.lower(...).compile().as_text()``).  An
    instruction without a scope of its own that calls a computation (a
    fusion) takes the scope of that computation's root."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        st = line.strip()
        if st.endswith("{") and " = " not in st:
            # a computation's header: "%name (...) -> ... {" or "ENTRY %name ..."
            m = re.search(r"%([\w.\-]+)", st)
            comp = m.group(1) if m else None
            continue
        if " = " not in st or not (st.startswith("%") or st.startswith("ROOT %")):
            continue
        root = st.startswith("ROOT ")
        name = st.split(" = ", 1)[0].replace("ROOT ", "").lstrip("%").strip()
        m = OP_NAME.search(st)
        own[name] = innermost_scope(m.group(1)) if m else None
        c = re.search(r"calls=%([\w.\-]+)", st)
        if c:
            calls[name] = c.group(1)
        if root and comp is not None:
            roots[comp] = name

    def scope(name: str, depth: int = 0) -> str | None:
        s = own.get(name)
        if s is None and name in calls and depth < 16:
            r = roots.get(calls[name])
            s = scope(r, depth + 1) if r else None
        return s

    return {k: s for k in own if (s := scope(k)) is not None}


def program_ops(trace: trace_reduce.Trace, program: str, lo: float, hi: float):
    """{plane: operations run inside one of ``program``'s module events in
    [lo, hi]} and the number of those module events."""
    pat = re.compile(rf"(^|[^A-Za-z0-9]|jit_){re.escape(program)}([^A-Za-z0-9_]|$)")
    out, calls = {}, 0
    for plane, mods in trace.modules.items():
        iv = sorted((m.start, m.end) for m in mods
                    if m.start >= lo and m.end <= hi and pat.search(m.name))
        calls += len(iv)
        starts = [s for s, _ in iv]
        keep = []
        for op in trace.ops.get(plane, []):
            mid = 0.5 * (op.start + op.end)
            k = bisect.bisect_right(starts, mid) - 1
            if k >= 0 and mid < iv[k][1]:
                keep.append(op)
        out[plane] = keep
    return out, calls


def scope_seconds(trace: trace_reduce.Trace, program: str, lo: float, hi: float,
                  hlo_scopes: dict[str, str]) -> tuple[dict[str, float], int]:
    """({scope: device self seconds}, calls) of ``program`` in [lo, hi],
    summed over device planes; ``hlo_scopes`` maps the program's instruction
    names to scopes (``scopes_from_hlo``).  An operation with none is
    ``other``."""
    per_plane, calls = program_ops(trace, program, lo, hi)
    tot: dict[str, float] = defaultdict(float)
    for ops in per_plane.values():
        for name, sec in trace_reduce.self_times(ops, lo, hi).items():
            tot[hlo_scopes.get(name, "other")] += sec
    return dict(tot), calls


# ----------------------------------------------------------------- summary


def reduce_layers(trace: trace_reduce.Trace, spans: list[Span],
                  queries: int | None = None, program: str | None = None,
                  hlo_scopes: dict | None = None) -> dict:
    """The per-layer numbers of one traced window: for a served cell (given
    ``queries``) milliseconds per query of each of ``SERVED_LAYERS``; for a
    program (given its name and ``hlo_scopes``) device milliseconds per call
    of each scope."""
    lo, hi = trace_reduce.window(trace)
    selfs = span_self_times(spans, lo, hi)
    counts = span_counts(spans, lo, hi)
    out = {
        "window_s": (hi - lo) / 1e9,
        "span_self_s": dict(sorted(selfs.items(), key=lambda kv: -kv[1])),
        "span_counts": counts,
        "idle_gaps": idle_gaps(trace, spans, lo, hi),
    }
    if queries:
        out["ms_per_query"] = {k: 1e3 * layer_seconds(selfs, names) / queries
                               for k, names in SERVED_LAYERS.items()}
        velo = sum(v for k, v in counts.items() if k.startswith("velo."))
        out["velo_spans_per_query"] = velo / queries
    if program:
        secs, calls = scope_seconds(trace, program, lo, hi, hlo_scopes)
        out["calls"] = calls
        if calls:
            out["scope_ms_per_call"] = {k: 1e3 * v / calls for k, v in sorted(secs.items())}
            out["device_ms_per_call"] = 1e3 * sum(
                sum(trace_reduce.named_times(m, program, lo, hi))
                for m in trace.modules.values()) / calls
    return out


# --------------------------------------------------------------------- CLI


def scan_hlo(config: dict, batch: int) -> str:
    """The compiled HLO text of the scan runner's ``scan_search`` at the
    cell's shapes: the program the trace ran, so the same instruction names."""
    import jax
    import jax.numpy as jnp

    from repro.velo.index import synthetic_specs
    from repro.velo.scan_search import scan_search

    sp = config["search"]
    q = jax.ShapeDtypeStruct((batch, config["d"]), jnp.float32)
    return scan_search.lower(
        synthetic_specs(config["n"], config["d"], 1), q, k=sp["k"],
        rerank=sp["rerank"], use_kernel=sp["use_kernel"], chunk=sp["chunk"],
    ).compile().as_text()


def run_traced(cell_name: str, seed: int, seconds: float, log=print,
               overrides: dict | None = None, **run_kw) -> dict:
    """One traced run of a cell through ``harness.run_cell``, its trace also
    reduced by layer: ``{"result": the result line's object, "layers": ...}``.
    ``overrides`` and ``run_kw`` go to ``run_cell``."""
    from bench import harness

    cell = harness.merge(harness.load_json(harness.BENCH_DIR, "workloads", cell_name),
                         (overrides or {}).get("cell"))
    config = harness.merge(harness.load_json(harness.BENCH_DIR, "configs", cell["config"]),
                           (overrides or {}).get("config"))
    kept: dict = {}
    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_keep(log_dir, **kw):
        red = reduce_dir(log_dir, **kw)
        t0 = time.time()
        kept["trace"], kept["spans"] = red["trace"], load_spans(log_dir)
        kept["load_s"] = time.time() - t0
        return red

    trace_reduce.reduce_dir = reduce_and_keep
    try:
        res = harness.run_cell(cell_name, seed, seconds, True, log=log,
                               overrides=overrides, **run_kw)
    finally:
        trace_reduce.reduce_dir = reduce_dir
    t0 = time.time()
    program = hlo_scopes = None
    if config["path"] == "scan" and cell["loop"]["kind"] == "closed":
        # one batch size, so one compiled program to name the operations of
        program = "scan_search"
        hlo_scopes = scopes_from_hlo(scan_hlo(config, cell["loop"]["batch"]))
    queries = res["attempted"] if config["path"] == "served" else None
    layers = reduce_layers(kept["trace"], kept["spans"], queries=queries,
                           program=program, hlo_scopes=hlo_scopes)
    layers["reduce_s"] = kept["load_s"] + time.time() - t0
    return {"result": res, "layers": layers}


def main(argv=None) -> int:
    import argparse
    import json

    import jax

    from bench import harness

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    ap = argparse.ArgumentParser(description="Run one cell traced; print its layers.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = run_traced(args.workload, args.seed, args.seconds, log=log, t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out["layers"]["run_s"] = time.time() - T_START
    for k, v in out["layers"].items():
        log(f"[layers] {k}: {v}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
