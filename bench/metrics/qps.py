"""Queries answered in the window over the window's wall time (host clock).
A closed loop's window runs from the first call's start to the last call's
end; an open loop's from the schedule's start to the last answer."""

UNIT = "queries/s"


def read(run):
    w = run.window
    return len(w.pool_idx) / w.seconds if w.seconds > 0 else None
