"""Share of the roofline reached by the sharded scan: per call, the least
time one chip could take for its shard's work over the time of that call's
slowest chip; the mean over the window's calls.

The least time is ``scan_roofline.least_seconds`` for B queries over one
shard's n/shards rows (d dimensions, C candidates), loaded from
``scan_roofline.py`` beside this file, so that the one-chip and the sharded
cells count the work one way: the level-1 inner products and the int4
rerank against the level-1 codes, the rerank rows and the queries.  It
counts what the deployment needs, whatever implements it; the merge of
shards x k candidates adds nothing worth counting.

A call's time is its ``sharded_scan`` module event on each chip's plane
(``bench.shard_trace``); the slowest chip sets the pace of the call.
Nothing is returned where the trace holds no such program.
"""

import importlib.util
import os

UNIT = "%"

PROGRAM = "sharded_scan"


def _scan_roofline():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "scan_roofline.py")
    spec = importlib.util.spec_from_file_location("bench_metrics_scan_roofline_shared", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run):
    from bench import shard_trace, trace_reduce

    c = run.counters
    if run.trace is None or run.peaks is None or "shard_n" not in c or not c["batch_rows"]:
        return None
    tr = run.trace["trace"]
    lo, hi = trace_reduce.window(tr)
    calls = shard_trace.program_calls(tr, PROGRAM, lo, hi)
    if not calls:
        return None
    least = _scan_roofline().least_seconds
    rows = c["batch_rows"]
    C = min(c["scan_rerank"], c["shard_n"])
    t_least = sum(least(B, c["shard_n"], c["scan_d"], C, run.peaks)[0] for B in rows) / len(rows)
    return 100.0 * sum(t_least / max(call) for call in calls) / len(calls)
