"""Share of the roofline reached by the scan: the least time the chip could
take for the window's ``scan_search`` calls over their device time.

The least time of one call is the larger of its operations over the bf16
peak and its bytes over the HBM bandwidth (``bench/peaks.json``).  Both count
only what the algorithm needs, so the share reads the same whatever
implements the scan.  For B queries, n rows, d dimensions, C candidates:

- operations: the level-1 inner products, 2*B*n*d, and the int4 rerank,
  2*B*C*d;
- bytes: the level-1 codes with their two float factors, n*(d/8 + 8), the
  rerank rows with their two float factors, B*C*(d/2 + 8), and the queries,
  4*B*d.

The device time is the sum of the trace's ``scan_search`` program events in
the window.  Nothing is returned where the trace holds none.
"""

UNIT = "%"

PROGRAM = "scan_search"


def ops(B: int, n: int, d: int, C: int) -> float:
    return 2.0 * B * n * d + 2.0 * B * C * d


def bytes_moved(B: int, n: int, d: int, C: int) -> float:
    return n * (d / 8 + 8) + B * C * (d / 2 + 8) + 4.0 * B * d


def least_seconds(B, n, d, C, peaks) -> tuple[float, str]:
    t_ops = ops(B, n, d, C) / peaks["bf16_flops"]
    t_mem = bytes_moved(B, n, d, C) / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def read(run):
    from bench import trace_reduce

    if run.trace is None or run.peaks is None or "scan_n" not in run.counters:
        return None
    tr = run.trace["trace"]
    lo, hi = trace_reduce.window(tr)
    plane = next(iter(tr.modules), None)
    if plane is None:
        return None
    times = trace_reduce.named_times(tr.modules[plane], PROGRAM, lo, hi)
    rows = run.counters["batch_rows"]
    if not times or not rows:
        return None
    c = run.counters
    least = [least_seconds(B, c["scan_n"], c["scan_d"], min(c["scan_rerank"], c["scan_n"]),
                           run.peaks)[0] for B in rows]
    # per call: a trace may hold fewer program events than calls were made
    return 100.0 * (sum(least) / len(least)) / (sum(times) / len(times))
