"""1 - (union of device operation intervals / traced window), from the
profiler trace (``bench.trace_reduce``)."""

UNIT = "%"


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    return 100.0 * run.trace["idle_share"]
