"""Fused on-device beam step: one dispatch per hop, no distance download.

The host beam loop round-trips every hop twice: upload ids, download raw
distances, insert into the beam on the host, pick the next frontier, repeat.
With ``SystemConfig.device_beam`` the per-query beam state lives on the
engine and one fused call per hop executes score -> visited mask -> top-k
merge -> frontier selection, returning only the (tiny) next frontier
(docs/beam_step.md).

Claims checked (the PR's acceptance bar):

  * PARITY — at B=1 / n_workers=1 the device plane returns bitwise-identical
    results (ids, dists, hops) to the host plane for ALL FIVE algorithms,
    fuse on and off (velo's hop count is excluded under fuse: its
    cache-aware pivot reads the simulated clock, so fuse alone already
    shifts the trajectory on the pure host plane — ids/dists stay bitwise);
  * EXCHANGE — distance downloads per query collapse to ~the refine stream
    (<= ~1.15x mean hops) with device_beam, and to <= ~0.6x the host
    plane's total (the estimate stream no longer ships raw distances);
  * THROUGHPUT — QPS with device_beam is no worse than the host plane at
    equal recall (recall drift <= 0.02);
  * a ``compiled_vs_interpret`` timing record for the fused step itself,
    so results.json separates real-accelerator runs from CPU interpret mode.

Standalone:  python -m benchmarks.bench_beam_step [--full] [--strict]
(--strict exits non-zero when any claim check fails, same contract as
benchmarks/run.py --strict.)
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmarks import common
from repro.core import baselines
from repro.core import beam as beam_mod
from repro.core import dataset as dataset_mod
from repro.core import distance as distance_mod
from repro.core import vamana as vamana_mod
from repro.core.quant import RabitQuantizer
from repro.core.search import ALGORITHMS, SearchParams

ALGOS = sorted(ALGORITHMS)
# velo's cache-aware pivot (acc.resident) reads the simulated clock, so its
# TRAJECTORY (hops) is timing-dependent whenever charges change — fuse alone
# already shifts it on the pure host plane.  Under fuse its parity bar is
# ids/dists; hops are bitwise only on the charge-identical fuse-off path.
TIMING_DEPENDENT = {"velo"}
RECALL_DRIFT = 0.02
QPS_FLOOR = 0.98
DOWNLOAD_CEIL = 1.15   # device: downloads/query <= ceil * mean hops
DOWNLOAD_HALVING = 0.6  # device downloads <= this fraction of host's


def _parity_fixture():
    ds = dataset_mod.make_dataset(n=600, d=32, n_queries=12, k=10, seed=4)
    graph = vamana_mod.build_vamana(ds.base, R=12, L=24, batch_size=256,
                                    seed=4)
    qb = RabitQuantizer(32, seed=4).fit_encode(ds.base)
    return ds, graph, qb


def _parity_sweep() -> dict[str, bool]:
    """device_beam vs host, bitwise, per algorithm (both fuse modes)."""
    ds, graph, qb = _parity_fixture()

    def run(algo, device_beam, fuse):
        # batch_size=1: the bitwise contract holds for SERIAL queries —
        # interleaved coroutines shift velo's timing-dependent cache pivot
        # (docs/beam_step.md), where parity is recall-level, not bitwise
        cfg = baselines.SystemConfig(
            buffer_ratio=0.2, n_workers=1, batch_size=1, fuse=fuse,
            device_beam=device_beam, params=SearchParams(L=24, W=4),
        )
        sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
        results, stats = sys_.run(ds.queries)
        return results, stats

    out = {}
    for algo in ALGOS:
        ok = True
        for fuse in (False, True):
            ref, _ = run(algo, False, fuse)
            got, got_stats = run(algo, True, fuse)
            with_hops = not (fuse and algo in TIMING_DEPENDENT)
            ok &= [
                (list(r.ids), list(r.dists), r.hops if with_hops else None)
                for r in got
            ] == [
                (list(r.ids), list(r.dists), r.hops if with_hops else None)
                for r in ref
            ]
            ok &= got_stats.beam_ops > 0
        out[algo] = ok
    return out


def _exchange_sweep(quick: bool) -> dict:
    """Downloads/query and QPS, host vs device plane, per algorithm."""
    if quick:
        w = common.Workload("beamq", n=3000, d=64, n_queries=96, R=16,
                            L=32, seed=7)
        params = SearchParams(L=32, W=4)
    else:
        w = common.Workload("beam", n=8000, d=96, n_queries=192, R=24,
                            L=48, seed=7)
        params = SearchParams(L=48, W=4)

    rows = {}
    for algo in ALGOS:
        per = {}
        for device_beam in (False, True):
            cfg = baselines.SystemConfig(
                buffer_ratio=0.2, n_workers=2, batch_size=4,
                device_beam=device_beam, params=params,
            )
            sys_ = baselines.build_system(algo, w.ds.base, w.graph, w.qb,
                                          cfg)
            m = baselines.evaluate(sys_, w.ds)
            m["downloads_per_hop"] = (
                m["downloads_per_query"] / max(m["mean_hops"], 1e-9)
            )
            per["device" if device_beam else "host"] = m
        per["qps_ratio"] = per["device"]["qps"] / per["host"]["qps"]
        per["recall_drift"] = abs(
            per["device"]["recall@k"] - per["host"]["recall@k"]
        )
        rows[algo] = per
    return rows


def _fused_step_timing() -> dict:
    """compiled-vs-interpret wall clock of ONE fused beam step on the
    pallas engine."""
    rng = np.random.default_rng(0)
    n, d, rows = 2048, 64, 256
    base = rng.standard_normal((n, d)).astype(np.float32)
    qb = RabitQuantizer(d, seed=0).fit_encode(base)
    pq = RabitQuantizer.prepare_query(
        qb, rng.standard_normal(d).astype(np.float32)
    )
    eng = distance_mod.get_engine("pallas")
    state = eng.beam_new(64, n)
    req = beam_mod.BeamRequest(
        kind="estimate", state=state,
        fresh=rng.integers(0, n, rows).astype(np.int64),
        explored=np.zeros(0, np.int64),
        insert_ids=np.zeros(0, np.int64),
        insert_ds=np.zeros(0, np.float32),
        rows=rows, flop_s=0.0, pq=pq, qb=qb,
    )
    native = eng.interpret

    def make_fn(interpret):
        def fn():
            eng.interpret = interpret
            try:
                eng.beam_step_many(qb, [req])
            finally:
                eng.interpret = native
        return fn

    rec = common.compiled_vs_interpret(make_fn, reps=3, mode=native)
    rec["rows"] = rows
    return rec


def run(quick: bool = True) -> dict:
    parity = _parity_sweep()
    exchange = _exchange_sweep(quick)
    timing = _fused_step_timing()

    rows = []
    for algo, per in exchange.items():
        h, d = per["host"], per["device"]
        rows.append([
            algo, f"{h['downloads_per_hop']:.2f}",
            f"{d['downloads_per_hop']:.2f}",
            f"{h['qps']:.0f}", f"{d['qps']:.0f}",
            f"{per['qps_ratio']:.2f}", f"{d['recall@k']:.3f}",
            d["beam_ops"],
        ])
    text = common.fmt_table(
        ["algo", "dl/hop host", "dl/hop dev", "QPS host", "QPS dev",
         "ratio", "recall", "beam ops"],
        rows,
    )
    text += "\nB=1 bitwise parity: " + "  ".join(
        f"{a}={'ok' if ok else 'FAIL'}" for a, ok in parity.items()
    )
    if timing:
        text += (
            f"\nfused step ({timing['rows']} rows): compiled "
            f"{timing['compiled_s'] * 1e6:.1f}us"
            + (f"  interpret {timing['interpret_s'] * 1e6:.1f}us"
               if timing["interpret_s"] is not None else "")
            + f"  (pallas_interpret={timing['pallas_interpret']})"
        )

    checks = {
        # device plane returns the host plane's exact results
        **{f"parity_{a}": ok for a, ok in parity.items()},
        # the estimate stream stops shipping raw distances to the host
        "downloads_collapse_with_device_beam": all(
            per["device"]["downloads_per_hop"] <= DOWNLOAD_CEIL
            for per in exchange.values()
        ),
        "downloads_halved_vs_host": all(
            per["device"]["downloads_per_query"]
            <= DOWNLOAD_HALVING * per["host"]["downloads_per_query"]
            for per in exchange.values()
        ),
        # no-regression bar: at equal recall, the fused plane is no slower
        "qps_no_worse": all(
            per["qps_ratio"] >= QPS_FLOOR for per in exchange.values()
        ),
        "recall_flat": all(
            per["recall_drift"] <= RECALL_DRIFT for per in exchange.values()
        ),
        "beam_path_active": all(
            per["device"]["beam_ops"] > 0 for per in exchange.values()
        ),
    }
    return {
        "name": "device_beam_step",
        "results": {
            "parity": parity,
            "exchange": exchange,
            "fused_step_timing": timing,
        },
        "text": text,
        "checks": checks,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="quick profile (the default; kept explicit for CI)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 if any claim check fails")
    args = ap.parse_args()
    res = run(quick=not args.full)
    print(res["text"])
    ok = True
    for check, passed in res["checks"].items():
        ok &= bool(passed)
        print(f"  [{'PASS' if passed else 'FAIL'}] {check}")
    if args.strict and not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
