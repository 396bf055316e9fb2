"""95th percentile over all requests of the window of the time from each
request's due time on the arrival schedule to its answer on the host."""

import numpy as np

UNIT = "ms"


def read(run):
    lat = run.window.latency_s
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(lat, 95) * 1e3)
