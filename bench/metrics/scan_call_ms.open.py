"""Median host-clock time of one blocked ``scan_search`` call in the window
(host-to-device copy of the batch included)."""

import numpy as np

UNIT = "ms"


def read(run):
    calls = run.window.call_s
    return float(np.median(calls) * 1e3) if calls else None
