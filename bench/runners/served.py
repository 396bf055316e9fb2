"""Runner ``served``: the program's serving path, ``baselines.build_system(
"velo", ...).run(queries)`` (what ``repro.launch.serve`` drives), over a
Vamana graph and RaBitQ codes built by the program from the seeded corpus.

The configuration's ``index`` gives the build (R, L) and ``serving`` the
system's options; ``search`` gives L, W and k.  The graph and codes are kept
in the index cache, keyed by the build code's hash.
"""

from __future__ import annotations

import gc

import numpy as np

from bench import gen, index_cache
from bench.spans import span


def _build_code() -> list[str]:
    import repro.core.quant
    import repro.core.vamana

    return [repro.core.vamana.__file__, repro.core.quant.__file__, gen.__file__]


class Served:
    def __init__(self, system, base, k):
        self.system, self._base, self.k = system, base, k
        self.reset_counters()

    def search(self, q: np.ndarray):
        dist = self.system.ctx.dist.stats
        d0 = dist.dispatches()
        with span("bench.engine_run"):
            results, stats = self.system.run(q)
        c = self._c
        c["queries"] += len(results)
        c["dispatches"] += dist.dispatches() - d0
        c["hbm_hits"] += stats.hbm_hits
        c["hbm_misses"] += stats.hbm_misses
        ids = np.full((len(q), self.k), -1, np.int64)
        d2 = np.full((len(q), self.k), np.nan)
        for i, r in enumerate(results):
            m = min(self.k, len(r.ids))
            ids[i, :m] = r.ids[:m]
            d2[i, :m] = r.dists[:m]
        return ids, d2

    def reset_counters(self):
        self._c = {"queries": 0, "dispatches": 0, "hbm_hits": 0, "hbm_misses": 0}

    def counters(self) -> dict:
        return dict(self._c)

    def free(self):
        self.system = None
        gc.collect()

    def base(self) -> np.ndarray:
        return self._base


def build_index(config: dict, seed: int, base: np.ndarray):
    """(graph, codes) of the seeded corpus ``base``, from the index cache or
    built by the program and stored there."""
    from repro.core import vamana
    from repro.core.quant import RabitQuantizer

    ix, ps = config["index"], gen.program_seed(seed)

    def build():
        graph = vamana.build_vamana(base, R=ix["R"], L=ix["L_build"], seed=ps)
        qb = RabitQuantizer(config["d"], seed=ps).fit_encode(base)
        return graph, qb

    key = {"n": config["n"], "d": config["d"], "index": ix}
    built, _ = index_cache.cached(key, seed, index_cache.code_hash(_build_code()), build)
    return built


def setup(config: dict, seed: int) -> Served:
    from repro.core import baselines

    sv, sp = config["serving"], config["search"]
    base = gen.make_base(seed, config["n"], config["d"])
    ps = gen.program_seed(seed)
    graph, qb = build_index(config, seed, base)
    cfg = baselines.SystemConfig(
        buffer_ratio=sv["buffer_ratio"], batch_size=sv["batch"],
        n_workers=sv["workers"],
        params=baselines.SearchParams(L=sp["L"], W=sp["W"], k=sp["k"]),
        seed=ps, distance_backend=sv["distance_backend"],
        device_beam=sv["device_beam"], hbm_tier=sv["hbm_tier"],
        fuse=sv["fuse"], shared_rendezvous=sv["shared_rendezvous"],
    )
    system = baselines.build_system("velo", base, graph, qb, cfg)
    return Served(system, base, sp["k"])
