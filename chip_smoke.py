#!/usr/bin/env python3
"""Bring-up smoke of the VeloANN search path on a TPU.

  python chip_smoke.py              # one chip: phases (a)-(c) below
  python chip_smoke.py --chips 4    # four chips: the sharded scan only

One process runs every phase in order and any failure exits non-zero.  All
data is generated from --seed in this run; nothing is read from disk.

  (a) device   platform, device_kind and count; the Pallas engine compiles
               its kernels (interpret mode off).
  (b) served   the serving entry point, ``repro.launch.serve.main``, on a
               DEEP-shaped corpus (d=96, L2) with the device features on:
               pallas distance engine, fused on-device beam step, HBM record
               tier, fused dispatch over a shared rendezvous, 4 workers,
               batch 8.  recall@10 against exact top-10 (``flat.exact_topk``)
               must reach SERVED_RECALL_FLOOR and lie within RECALL_GAP of
               the same queries on the NumPy ``batch`` engine.  The corpus is
               cut to what the host Vamana build finishes in a few minutes.
  (c) scan     ``velo.scan_search`` compiled over a 1M x 96 corpus whose
               level-1/level-2 tables live in HBM, 256 queries, recall@10
               against exact top-10 at SCAN_RECALL_FLOOR.
  --chips 4    ``dist_search.make_distributed_search(mode="scan")`` over a
               4-chip mesh, each chip holding its own shard, compared with
               the same per-shard scans run in turn on one chip and merged by
               ``merge_topk``: top-10 ids agree up to exact distance ties.

Counts and recalls are printed; wall seconds are host-clock phase times
(compiles included), not device throughput.  The last line of stdout is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.  Where
JAX's first device is not a TPU the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

DIM = 96                  # DEEP1B width (big-ann-benchmarks 2021)
QUERIES = 256
K = 10
# Host Vamana build (R=32, L=64, two passes) runs ~12 ms per vertex on one
# core: 10k vectors take about two minutes.  Device-side build lifts this.
SERVED_N = 10_000
SCAN_N = 1_000_000        # ~76 MB of level-1 + level-2 tables in HBM
RERANK = 512              # scan stage-2 candidates (0.05% of the corpus)

# Floors sit below the NumPy reference engine's recall on the same data
# (0.714 served at 10k; 0.686 scan at 400k with RERANK=512, falling with n).
SERVED_RECALL_FLOOR = 0.65
RECALL_GAP = 0.02
SCAN_RECALL_FLOOR = 0.50


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


def phase_device():
    import jax

    from repro.core.distance import PallasEngine

    devs = jax.devices()
    log(f"[a] platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    check(PallasEngine().interpret is False, "PallasEngine compiles its kernels")


def phase_served(n: int, n_queries: int, seed: int) -> None:
    from repro.launch import compile_cache, serve

    argv = ["--n", str(n), "--d", str(DIM), "--queries", str(n_queries),
            "--seed", str(seed), "--workers", "4", "--batch", "8",
            "--device-beam", "--hbm-tier", "--fuse", "--shared-rendezvous"]
    t0 = time.time()
    index = serve.build_index(serve.parse_args(argv))
    log(f"[b] reduced: served corpus n={n} (host Vamana build "
        f"{time.time() - t0:.1f}s wall)")

    with compile_cache.CompileCounter() as cc:
        t0 = time.time()
        dev = serve.main(argv + ["--backend", "pallas"], index=index)
        wall = time.time() - t0
    log(f"[b] pallas: recall@10={dev['recall@k']:.4f} "
        f"dist_dispatches={dev['dist_dispatches']} "
        f"beam_steps={dev['beam_steps']} hbm_hits={dev['hbm_hits']} "
        f"dist_uploads={dev['dist_uploads']} compiles={cc.compiles} "
        f"cache_hits={cc.cache_hits} wall_s={wall:.1f}")
    check(dev["distance_backend"] == "pallas", "served on the pallas engine")
    check(dev["beam_steps"] > 0, "fused beam steps ran")
    check(dev["hbm_hits"] > 0, "HBM tier served records")
    check(dev["recall@k"] >= SERVED_RECALL_FLOOR,
          f"served recall@10 {dev['recall@k']:.4f} >= {SERVED_RECALL_FLOOR}")

    t0 = time.time()
    ref = serve.main(argv + ["--backend", "batch"], index=index)
    log(f"[b] batch:  recall@10={ref['recall@k']:.4f} "
        f"wall_s={time.time() - t0:.1f}")
    gap = abs(dev["recall@k"] - ref["recall@k"])
    check(gap <= RECALL_GAP, f"pallas vs batch recall gap {gap:.4f} <= {RECALL_GAP}")


def _scan_corpus(n: int, n_queries: int, seed: int):
    from repro.core import dataset
    from repro.core.quant import RabitQuantizer

    ds = dataset.make_dataset(n=n, d=DIM, n_queries=n_queries, k=K, seed=seed)
    qb = RabitQuantizer(DIM, seed=seed).fit_encode(ds.base)
    return ds, qb


def phase_scan(n: int, n_queries: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dataset import recall_at_k
    from repro.launch import compile_cache
    from repro.velo.index import from_host
    from repro.velo.scan_search import scan_search

    t0 = time.time()
    ds, qb = _scan_corpus(n, n_queries, seed)
    index = from_host(qb)
    table_mb = sum(
        getattr(index, f).nbytes for f in
        ("binary_codes", "norms", "ip_bar", "ext_codes", "ext_lo", "ext_step")
    ) / 1e6
    log(f"[c] corpus n={n} d={DIM}: tables {table_mb:.1f} MB on device "
        f"(set-up {time.time() - t0:.1f}s wall)")
    with compile_cache.CompileCounter() as cc:
        t0 = time.time()
        ids, d2 = scan_search(index, jnp.asarray(ds.queries), k=K, rerank=RERANK)
        ids = np.asarray(jax.block_until_ready(ids))
        wall = time.time() - t0
    rec = recall_at_k(ids, ds.groundtruth, K)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[c] scan: recall@10={rec:.4f} compiles={cc.compiles} "
        f"cache_hits={cc.cache_hits} wall_s={wall:.1f} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    check(bool(np.isfinite(np.asarray(d2)).all()), "finite scan distances")
    check(rec >= SCAN_RECALL_FLOOR, f"scan recall@10 {rec:.4f} >= {SCAN_RECALL_FLOOR}")


def topk_agree(ids_a, d_a, ids_b, d_b, rtol=1e-5, atol=1e-5) -> bool:
    """Row-wise equal top-k ids, except where the two differ only among
    candidates at exactly tied distances."""
    import numpy as np

    for ia, da, ib, db in zip(ids_a, d_a, ids_b, d_b):
        if np.array_equal(ia, ib):
            continue
        if not np.allclose(da, db, rtol=rtol, atol=atol):
            return False
        for pos in np.nonzero(ia != ib)[0]:
            ties = np.isclose(da, da[pos], rtol=rtol, atol=atol).sum()
            if ties < 2 and pos != len(da) - 1:
                return False
    return True


def phase_sharded(n: int, n_queries: int, seed: int, devices) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.dataset import recall_at_k
    from repro.velo import dist_search
    from repro.velo.index import DeviceIndex, from_host
    from repro.velo.scan_search import scan_search

    S = len(devices)
    check(n % S == 0, f"corpus n={n} splits evenly over {S} chips")
    per = n // S
    t0 = time.time()
    ds, qb = _scan_corpus(n, n_queries, seed)
    fields = ("binary_codes", "norms", "ip_bar", "ext_codes", "ext_lo", "ext_step")
    parts = [
        from_host(dataclasses.replace(
            qb, **{f: getattr(qb, f)[s * per:(s + 1) * per] for f in fields}))
        for s in range(S)
    ]  # one shard after another, each with its own sentinel row
    offsets = np.arange(S, dtype=np.int32) * per
    queries = jnp.asarray(ds.queries)
    log(f"[4] corpus n={n} d={DIM} over {S} shards of {per} "
        f"(set-up {time.time() - t0:.1f}s wall)")

    # reference: each shard's scan on one chip in turn, then the same merge
    t0 = time.time()
    g_all, d_all = [], []
    for s, part in enumerate(parts):
        ids, d2 = scan_search(part, queries, k=K, rerank=RERANK)
        g, d = dist_search.mask_local_topk(ids, d2, jnp.int32(offsets[s]))
        g_all.append(g)
        d_all.append(d)
    ref_ids, ref_d = dist_search.merge_topk(
        jnp.concatenate(g_all, axis=1), jnp.concatenate(d_all, axis=1), K)
    ref_ids, ref_d = np.asarray(ref_ids), np.asarray(ref_d)
    log(f"[4] one chip, shards in turn: wall_s={time.time() - t0:.1f}")

    mesh = jax.sharding.Mesh(np.asarray(devices), ("shards",))
    split, whole = NamedSharding(mesh, P("shards")), NamedSharding(mesh, P())
    index = DeviceIndex(
        centroid=jax.device_put(parts[0].centroid, whole),
        rotation=jax.device_put(parts[0].rotation, whole),
        medoid=jax.device_put(parts[0].medoid, whole),
        **{f: jax.device_put(jnp.concatenate([getattr(p, f) for p in parts]), split)
           for f in fields + ("adjacency",)},
    )
    shard_devs = {sh.device for sh in index.binary_codes.addressable_shards}
    check(len(shard_devs) == S, f"{S} shards on {len(shard_devs)} distinct devices")
    check(all(sh.data.shape[0] == per + 1
              for sh in index.binary_codes.addressable_shards),
          "each device holds exactly its shard")
    search = jax.jit(dist_search.make_distributed_search(
        mesh, ("shards",), mode="scan", L=RERANK, k=K))
    t0 = time.time()
    ids, d2 = search(index, jax.device_put(jnp.asarray(offsets), split),
                     jax.device_put(queries, whole))
    ids, d2 = np.asarray(ids), np.asarray(d2)
    log(f"[4] sharded over {S} chips: wall_s={time.time() - t0:.1f}")

    same = int((ids == ref_ids).all(axis=1).sum())
    rec = recall_at_k(ids, ds.groundtruth, K)
    log(f"[4] rows with identical top-{K} ids: {same}/{len(ids)}; "
        f"recall@10={rec:.4f}")
    check(topk_agree(ids, d2, ref_ids, ref_d),
          "sharded top-10 == per-shard scans merged on one chip, up to ties")
    check(rec >= SCAN_RECALL_FLOOR, f"sharded recall@10 {rec:.4f} >= {SCAN_RECALL_FLOOR}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1

    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    t0 = time.time()
    if args.chips == 4:
        phase_sharded(SCAN_N, QUERIES, args.seed, devs[:4])
    else:
        phase_device()
        phase_served(SERVED_N, QUERIES, args.seed)
        phase_scan(SCAN_N, QUERIES, args.seed)
    log(f"total wall_s={time.time() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
