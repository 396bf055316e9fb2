"""Runner ``scan``: the program's HBM-resident compressed scan,
``velo.scan_search.scan_search`` (binary level-1 sweep, then int4 rerank of
``rerank`` candidates), over RaBitQ codes the program builds from the seeded
corpus and ``velo.index.from_host`` places on the chip.

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


class Scan:
    def __init__(self, index, config, seed, base):
        import jax

        self.index, self.config, self.seed, self._base = index, config, seed, base
        sp = config["search"]
        self.k, self.rerank, self.use_kernel = sp["k"], sp["rerank"], sp["use_kernel"]
        self.chunk = sp["chunk"]
        self._put = jax.device_put
        self.reset_counters()

    def search(self, q: np.ndarray):
        from repro.velo.scan_search import scan_search

        with span("bench.h2d"):
            qd = self._put(q)
        with span("bench.scan_call"):
            ids, d2 = scan_search(self.index, qd, k=self.k, rerank=self.rerank,
                                  use_kernel=self.use_kernel, chunk=self.chunk)
            ids, d2 = np.asarray(ids), np.asarray(d2)
        self._c["calls"] += 1
        self._c["batch_rows"].append(len(q))
        return ids, d2

    def reset_counters(self):
        self._c = {"calls": 0, "batch_rows": []}

    def counters(self) -> dict:
        return {"calls": self._c["calls"], "batch_rows": list(self._c["batch_rows"]),
                "scan_n": self.config["n"], "scan_d": self.config["d"],
                "scan_rerank": self.rerank}

    def free(self):
        self.index = None
        gc.collect()

    def base(self) -> np.ndarray:
        if self._base is None:
            self._base = gen.make_base(self.seed, self.config["n"], self.config["d"])
        return self._base


def setup(config: dict, seed: int) -> Scan:
    import jax

    from repro.core.quant import RabitQuantizer
    from repro.velo.index import from_host

    n, d = config["n"], config["d"]
    built = {}

    def build():
        built["base"] = gen.make_base(seed, n, d)
        return RabitQuantizer(d, seed=gen.program_seed(seed)).fit_encode(built["base"])

    qb, _ = index_cache.cached({"n": n, "d": d, "index": config["index"]}, seed,
                               index_cache.code_hash(_build_code()), build)
    index = jax.block_until_ready(from_host(qb))
    del qb
    return Scan(index, config, seed, built.get("base"))
