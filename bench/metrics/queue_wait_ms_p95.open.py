"""95th percentile over the window's requests of the time from a request's
due time to the start of the call that serves it (the micro-batcher's wait)."""

import numpy as np

UNIT = "ms"


def read(run):
    w = run.window.queue_wait_s
    if w is None or len(w) == 0:
        return None
    return float(np.percentile(w, 95) * 1e3)
