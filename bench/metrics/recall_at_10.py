"""Recall@10 of every answer of the window against exact top-10 by brute
force in float32 (``bench.gen.exact_topk``)."""

UNIT = "fraction"


def read(run):
    return run.numbers["recall_at_10"]
