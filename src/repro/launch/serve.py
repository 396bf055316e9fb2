"""Serving CLI: the end-to-end VeloANN driver (the paper is a serving system).

Builds the compressed index over a synthetic corpus, then pushes a batched
query stream through the asynchronous engine and reports the paper's
metrics (QPS / latency / recall / IO / hit rate).  QPS and latency are the
engine's modelled seconds, not device time.

  PYTHONPATH=src python -m repro.launch.serve --n 20000 --d 128 --queries 500

The device path: ``--backend pallas --device-beam --hbm-tier --fuse
--shared-rendezvous`` (compiled kernels on the TPU, interpret mode on CPU).
"""

from __future__ import annotations

import argparse
import time

from repro.core import baselines, dataset, distance, vamana
from repro.core.quant import RabitQuantizer
from repro.launch import compile_cache


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10000)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--queries", type=int, default=400)
    ap.add_argument("--system", default="velo",
                    choices=["velo", "diskann", "starling", "pipeann", "inmemory"])
    ap.add_argument("--buffer-ratio", type=float, default=0.2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--L", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="batch", choices=distance.BACKENDS,
                    help="distance engine")
    ap.add_argument("--device-beam", action="store_true",
                    help="fused on-device beam step")
    ap.add_argument("--hbm-tier", action="store_true",
                    help="device-resident record tier above the host pool")
    ap.add_argument("--fuse", action="store_true",
                    help="cross-query fused score dispatch")
    ap.add_argument("--shared-rendezvous", action="store_true",
                    help="one rendezvous buffer spanning all workers")
    return ap.parse_args(argv)


def build_index(args: argparse.Namespace):
    """Corpus, Vamana graph and quantized base for ``args``, from its seed."""
    t0 = time.time()
    print(f"[serve] generating corpus n={args.n} d={args.d} ...", flush=True)
    ds = dataset.make_dataset(n=args.n, d=args.d, n_queries=args.queries,
                              k=10, seed=args.seed)
    print(f"[serve] building Vamana graph ... ({time.time()-t0:.1f}s)", flush=True)
    graph = vamana.build_vamana(ds.base, R=32, L=64, seed=args.seed)
    qb = RabitQuantizer(args.d, seed=args.seed).fit_encode(ds.base)
    print(f"[serve] index built ({time.time()-t0:.1f}s)", flush=True)
    return ds, graph, qb


def main(argv=None, index=None):
    """Serve the query stream once; returns ``baselines.evaluate``'s record.
    ``index`` is a ``build_index`` result for the same --n/--d/--queries/
    --seed, to serve one index under several configurations."""
    args = parse_args(argv)
    compile_cache.enable()
    ds, graph, qb = index if index is not None else build_index(args)
    print(f"[serve] running {args.system} ...", flush=True)

    cfg = baselines.SystemConfig(
        buffer_ratio=args.buffer_ratio, batch_size=args.batch,
        n_workers=args.workers,
        params=baselines.SearchParams(L=args.L, W=4),
        seed=args.seed,
        distance_backend=args.backend,
        device_beam=args.device_beam,
        hbm_tier=args.hbm_tier,
        fuse=args.fuse,
        shared_rendezvous=args.shared_rendezvous,
    )
    system = baselines.build_system(args.system, ds.base, graph, qb, cfg)
    out = baselines.evaluate(system, ds)
    print(f"[serve] system={out['system']} backend={out['distance_backend']} "
          f"recall@10={out['recall@k']:.3f} io/q={out['ios_per_query']:.1f} "
          f"hit={out['hit_rate']:.2f} | modelled, not device time: "
          f"QPS={out['qps']:.0f} mean_lat={out['mean_latency_ms']:.2f}ms "
          f"p99={out['p99_latency_ms']:.2f}ms")
    print(f"[serve] disk={out['disk_bytes']/1e6:.1f}MB "
          f"memory={out['memory_bytes']/1e6:.1f}MB "
          f"(origin {ds.base.nbytes/1e6:.1f}MB)")
    return out


if __name__ == "__main__":
    main()
