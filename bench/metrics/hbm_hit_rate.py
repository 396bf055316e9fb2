"""Share of record accesses the HBM record tier served, over the window's
calls (``WorkloadStats.hbm_hits`` / (hits + misses))."""

UNIT = "%"


def read(run):
    c = run.counters
    total = c.get("hbm_hits", 0) + c.get("hbm_misses", 0)
    return 100.0 * c["hbm_hits"] / total if total else None
