#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its limit,
which also close standard error).  Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""

import os
import sys
import time

T_START = time.time()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
# The compilation cache sits at a fixed path inside the checkout, so only a
# cell's first run there compiles and two checkouts share nothing.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH_DIR, ".cache", "jax")
sys.path[:0] = [REPO_DIR, os.path.join(REPO_DIR, "src")]

import jax  # noqa: E402

jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
