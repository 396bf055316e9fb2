"""Device time of the shard merge, in milliseconds per call: the self time
of the operations under the program's ``velo.shard.merge`` scope (mask,
all-gathers, global top-k), summed over the chips and divided by the chips'
``sharded_scan`` calls, so the mean over chips.

The operations are named by the compiled program's HLO text (the runner's
``hlo_text`` counter) through ``trace_layers.scopes_from_hlo``; a chip that
reaches the all-gather first waits in it for the others, and that wait
counts here.  Nothing is returned where the trace holds no such program or
the program has no such scope.
"""

UNIT = "ms"

PROGRAM = "sharded_scan"
SCOPE = "velo.shard.merge"


def read(run):
    from bench import trace_layers, trace_reduce

    text = run.counters.get("hlo_text")
    if run.trace is None or not text:
        return None
    scopes = trace_layers.scopes_from_hlo(text)
    if SCOPE not in scopes.values():
        return None
    tr = run.trace["trace"]
    lo, hi = trace_reduce.window(tr)
    secs, calls = trace_layers.scope_seconds(tr, PROGRAM, lo, hi, scopes)
    if not calls:
        return None
    return 1e3 * secs.get(SCOPE, 0.0) / calls
