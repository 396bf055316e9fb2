"""Runner ``sharded_scan``: the program's sharded compressed scan,
``velo.dist_search.ShardedScan``, over the configuration's ``shards``
devices.  The program splits RaBitQ codes built from the seeded corpus into
equal shards, one per device with its own sentinel row; each call replicates
the batch to every device, scans each shard with ``scan_search`` and merges
the per-shard top-k with an all-gather.

The codes are kept in the index cache; the float corpus is not (it is
regenerated from the seed for the check when a cached run did not build it).
"""

from __future__ import annotations

import gc

import numpy as np

from bench import gen, index_cache
from bench.spans import span


def _build_code() -> list[str]:
    import repro.core.quant

    return [repro.core.quant.__file__, gen.__file__]


class Sharded:
    def __init__(self, entry, config, seed, base):
        self.entry, self.config, self.seed, self._base = entry, config, seed, base
        self.rerank = config["search"]["rerank"]
        self.reset_counters()

    def search(self, q: np.ndarray):
        with span("bench.h2d"):
            qd = self.entry.put(q)
        with span("bench.scan_call"):
            ids, d2 = self.entry.search(qd)
            ids, d2 = np.asarray(ids), np.asarray(d2)
        self._c["calls"] += 1
        self._c["batch_rows"].append(len(q))
        return ids, d2

    def reset_counters(self):
        self._c = {"calls": 0, "batch_rows": []}

    def counters(self) -> dict:
        c = self.config
        rows = list(self._c["batch_rows"])
        return {"calls": self._c["calls"], "batch_rows": rows,
                "shards": c["shards"], "shard_n": c["n"] // c["shards"],
                "scan_d": c["d"], "scan_rerank": self.rerank,
                # the compiled program of the window's batch size, whose
                # instruction names the trace's operations carry
                "hlo_text": self.entry.hlo_text(rows[-1]) if rows else None}

    def free(self):
        self.entry = None
        gc.collect()

    def base(self) -> np.ndarray:
        if self._base is None:
            self._base = gen.make_base(self.seed, self.config["n"], self.config["d"])
        return self._base


def setup(config: dict, seed: int) -> Sharded:
    import jax

    # first, so that a program without the sharded entry fails at once
    from repro.core.quant import RabitQuantizer
    from repro.velo.dist_search import ShardedScan

    n, d = config["n"], config["d"]
    built = {}

    def build():
        built["base"] = gen.make_base(seed, n, d)
        return RabitQuantizer(d, seed=gen.program_seed(seed)).fit_encode(built["base"])

    qb, _ = index_cache.cached({"n": n, "d": d, "index": config["index"]}, seed,
                               index_cache.code_hash(_build_code()), build)
    sp = config["search"]
    entry = ShardedScan(qb, jax.devices()[:config["shards"]], k=sp["k"],
                        rerank=sp["rerank"], chunk=sp["chunk"],
                        use_kernel=sp["use_kernel"])
    jax.block_until_ready(entry.index)
    del qb
    return Sharded(entry, config, seed, built.get("base"))
