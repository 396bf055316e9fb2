"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [--quick | --full] [--only NAME] [--backend NAME]
                           [--fuse] [--fuse-rows N] [--shared-rendezvous]
                           [--overlap-flush] [--hbm-tier] [--hbm-slots N]
                           [--device-beam] [--scheduler NAME] [--sla-ms MS]
                           [--calibration PATH] [--strict]

Writes benchmarks/out/results.json and prints each table with the paper
claims it validates.  The run exits non-zero when any module raised; --strict
also exits non-zero when any paper-claim check fails, so CI smoke steps turn
regressions into build failures.  --full uses the larger workloads (slower, tighter
match to the paper's regimes); default is the quick profile (--quick makes
that explicit).  --backend selects the DistanceEngine for every system
(scalar | batch | pallas); --fuse turns on cross-query fused score dispatch
(one kernel dispatch serving the frontiers of all coroutines in flight on a
worker), with --fuse-rows setting the rendezvous flush budget.  Each module's
record carries the active backend, the fuse settings, and its wall-clock
seconds so runs can be compared side by side.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import time
import traceback

from benchmarks import common
from repro.launch import compile_cache

MODULES = [
    "bench_hit_rate",        # Table 1
    "bench_access_skew",     # Fig 4
    "bench_fragmentation",   # Fig 6
    "bench_throughput",      # Fig 8 (+ Fig 1)
    "bench_batch_size",      # Fig 9
    "bench_beam_width",      # Fig 10
    "bench_thread_scaling",  # Fig 11
    "bench_buffer_ratio",    # Fig 12
    "bench_tau",             # Fig 13
    "bench_breakdown",       # Fig 14
    "bench_index_size",      # Table 3
    "bench_fusion",          # cross-query fused dispatch: B x fuse-budget sweep
    "bench_multitenant",     # serving plane: shared pool vs partition under skew
    "bench_sharded",         # sharded scatter-gather: S=1 parity + QPS scaling
    "bench_beam_step",       # fused on-device beam step: parity + exchange
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="quick profile (the default; kept explicit for CI)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--backend", default=None, choices=["scalar", "batch", "pallas"],
        help="DistanceEngine backend for all systems (default: batch)",
    )
    ap.add_argument("--fuse", action="store_true",
                    help="cross-query fused score dispatch for all systems")
    ap.add_argument("--fuse-rows", type=int, default=None,
                    help="rendezvous flush row budget (default 256)")
    ap.add_argument("--shared-rendezvous", action="store_true",
                    help="one system-wide rendezvous buffer spanning all "
                         "workers (implies --fuse)")
    ap.add_argument("--overlap-flush", action="store_true",
                    help="overlap the shared-rendezvous stall flush with "
                         "other workers' in-flight completions (implies "
                         "--shared-rendezvous)")
    ap.add_argument("--hbm-tier", action="store_true",
                    help="device-resident HBM record-cache tier above the "
                         "host pool for every record-pool system")
    ap.add_argument("--hbm-slots", type=int, default=None,
                    help="HBM tier slot count (default: match the host "
                         "pool's slot count)")
    ap.add_argument("--device-beam", action="store_true",
                    help="fused on-device beam step (score + visited mask + "
                         "top-k merge + frontier selection in one engine "
                         "call) for every system")
    ap.add_argument("--scheduler", default=None, choices=["rr", "sla"],
                    help="engine scheduling policy for every system "
                         "(rr: FIFO round-robin, the default; sla: "
                         "earliest-deadline-first + feedback steering)")
    ap.add_argument("--sla-ms", type=float, default=None,
                    help="per-query SLA in milliseconds (enables deadline "
                         "accounting; with --scheduler sla also the EDF "
                         "deadline and the feedback target)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="per-backend CostModel overrides from "
                         "benchmarks/calibrate.py (benchmarks/out/"
                         "calibration.json)")
    ap.add_argument("--strict", action="store_true",
                    help="also exit 1 if any paper-claim check fails")
    args = ap.parse_args()
    if args.quick and args.full:
        ap.error("--quick and --full are mutually exclusive")
    quick = not args.full
    compile_cache.enable()
    if args.backend:
        common.set_backend(args.backend)
    if (args.fuse or args.fuse_rows is not None or args.shared_rendezvous
            or args.overlap_flush):
        common.set_fuse(
            args.fuse or args.shared_rendezvous or args.overlap_flush,
            args.fuse_rows,
            shared=(args.shared_rendezvous or args.overlap_flush) or None,
            overlap=args.overlap_flush or None,
        )
    if args.hbm_tier or args.hbm_slots is not None:
        common.set_hbm(args.hbm_tier or args.hbm_slots is not None,
                       args.hbm_slots)
    if args.device_beam:
        common.set_device_beam(True)
    if args.scheduler or args.sla_ms is not None:
        common.set_scheduler(args.scheduler or "rr", args.sla_ms)
    if args.calibration:
        common.set_calibration(args.calibration)
    print(f"distance backend: {common.active_backend()}  fuse: {common.fuse_active()}"
          f"  hbm: {common.hbm_active()}"
          f"  device_beam: {common.device_beam_active()}"
          f"  scheduler: {common.scheduler_active()}")

    os.makedirs(common.OUT_DIR, exist_ok=True)
    results = {}
    n_checks = n_pass = n_errors = 0
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        mod = importlib.import_module(f"benchmarks.{modname}")
        t0 = time.time()
        try:
            res = mod.run(quick=quick)
        except Exception as e:  # noqa: BLE001
            res = {"name": modname, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:], "checks": {}}
        dt = time.time() - t0
        res["wall_clock_s"] = dt
        res["distance_backend"] = common.active_backend()
        # interpret vs compiled matters for pallas wall-clock comparisons
        res["pallas_interpret"] = common.pallas_mode()
        res["fuse"] = common.fuse_active()
        res["hbm"] = common.hbm_active()
        res["device_beam"] = common.device_beam_active()
        res["scheduler"] = common.scheduler_active()
        res["calibration"] = args.calibration
        results[modname] = res
        print(f"\n=== {res.get('name', modname)}  ({dt:.1f}s) ===")
        if "error" in res:
            print("ERROR:", res["error"])
            n_errors += 1
            continue
        print(res["text"])
        for check, ok in res.get("checks", {}).items():
            n_checks += 1
            n_pass += bool(ok)
            print(f"  [{'PASS' if ok else 'FAIL'}] {check}")

    path = os.path.join(common.OUT_DIR, "results.json")
    with open(path, "w") as f:
        json.dump(
            {k: {kk: vv for kk, vv in v.items() if kk != "text"}
             for k, v in results.items()},
            f, indent=1, default=float,
        )
    print(f"\n==== paper-claim checks: {n_pass}/{n_checks} pass ====")
    print(f"results -> {path}")
    if n_errors or (args.strict and n_pass < n_checks):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
