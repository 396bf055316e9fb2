"""Distance-plane dispatches (``DistanceStats.dispatches()``) in the window
per query answered: a count from the program's own counters."""

UNIT = "dispatch/query"


def read(run):
    c = run.counters
    if "dispatches" not in c or not c.get("queries"):
        return None
    return c["dispatches"] / c["queries"]
