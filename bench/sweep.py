#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then a window at each rate.

  python bench/sweep.py --workload gist960-flat.open --seed 7 --seconds 10 \
      --rates 2000,4000,8000

For each rate it prints the answered rate, the median and 95th-percentile
latency, and how late the last call started past the schedule's end.  A
backlog that grows through the window shows as a lateness of many call
times and a p95 that rises with the window; the knee is the highest rate
without one.  The benchmark's own runs do not use this; the cell's file
holds the rate chosen from it.
"""

import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH_DIR, ".cache", "jax")
sys.path[:0] = [os.path.dirname(BENCH_DIR), os.path.join(os.path.dirname(BENCH_DIR), "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from bench import gen, harness, loops  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    cell = harness.load_json(harness.BENCH_DIR, "workloads", args.workload)
    config = harness.load_json(harness.BENCH_DIR, "configs", cell["config"])
    harness.devices_for(cell["chips"], allow_cpu=False)
    runner = harness.load_module(harness.BENCH_DIR, "runners", config["path"])
    pool = gen.make_queries(args.seed, config["n"], config["d"], cell["pool"]["size"],
                            cell["pool"]["skew"])
    state = runner.setup(config, args.seed)
    loops.open_warm(state.search, pool, cell["loop"], args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        loop = dict(cell["loop"], rate_qps=rate)
        t0 = time.time()
        w = loops.open_window(state.search, pool, loop, args.seed + i, args.seconds)
        lat = w.latency_s * 1e3
        half = len(lat) // 2
        print(json.dumps({
            "rate_qps": rate, "requests": len(lat),
            "answered_qps": len(w.pool_idx) / w.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_half_ms": float(np.percentile(lat[:half], 95)),
            "p95_second_half_ms": float(np.percentile(lat[half:], 95)),
            "late_s": w.late_s, "calls": len(w.call_s),
            "call_ms_median": float(np.median(w.call_s) * 1e3),
            "mean_batch": len(lat) / max(1, len(w.call_s)),
            "wall_s": time.time() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
