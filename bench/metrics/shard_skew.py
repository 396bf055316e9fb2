"""How far the slowest chip lags: per call, 100 x (the slowest chip's
``sharded_scan`` program time / the mean over the chips - 1); the median
over the window's calls (``bench.shard_trace``).  Every chip waits in the
merge's all-gather for the last, so a chip that starts late or runs slow
sets every chip's time.  Nothing is returned where the trace holds no such
program on two chips or more."""

import statistics

UNIT = "%"

PROGRAM = "sharded_scan"


def read(run):
    from bench import shard_trace, trace_reduce

    if run.trace is None:
        return None
    tr = run.trace["trace"]
    lo, hi = trace_reduce.window(tr)
    calls = [c for c in shard_trace.program_calls(tr, PROGRAM, lo, hi) if len(c) > 1]
    if not calls:
        return None
    return statistics.median(100.0 * (max(c) / (sum(c) / len(c)) - 1.0) for c in calls)
