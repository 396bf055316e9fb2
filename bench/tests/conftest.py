"""Shared helpers of the benchmark's own tests: tiny overrides of each
configuration, so the CPU runs the harness end to end in seconds."""

import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), os.path.join(os.path.dirname(BENCH_DIR), "src")]

# Widths stay as configured; only scale, pools and windows shrink.
TINY = {
    "deep96-graph.zipf-closed": {
        "config": {"n": 800},
        "cell": {"pool": {"size": 96}},
    },
    "gist960-flat.open": {
        "config": {"n": 3072, "search": {"chunk": 1024}},
        "cell": {"pool": {"size": 48},
                 "loop": {"rate_qps": 2000, "buckets": [8, 16], "max_batch": 16,
                          "warmup_calls": 1}},
    },
    "gist960-flat.closed-b256": {
        "config": {"n": 3072, "search": {"chunk": 1024}},
        "cell": {"pool": {"size": 48}, "loop": {"batch": 16, "warmup_calls": 1}},
    },
}


@pytest.fixture(autouse=True)
def _index_cache_in_tmp(tmp_path, monkeypatch):
    """Keep test builds out of the benchmark's own index cache."""
    from bench import index_cache

    monkeypatch.setattr(index_cache, "CACHE_DIR", str(tmp_path / "index"))
