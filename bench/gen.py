"""The benchmark's own copy of the data generator and the exact-answer arithmetic.

Copied from the program so that no change to the program can move the
yardstick: ``make_base``/``make_queries`` follow ``repro.core.dataset.
make_dataset`` (clustered Gaussian corpus, queries near Zipf-skewed cluster
centres), ``exact_topk``/``exact_dist2`` follow ``repro.core.flat``,
``recall_at_10`` follows ``dataset.recall_at_k`` and ``CompileCounter``
follows ``repro.launch.compile_cache.CompileCounter``.  Nothing here imports
the program.
"""

from __future__ import annotations

import numpy as np

SEED_MOD = 2**63


# Streams of one seed: 0 cluster centres, 1 corpus, 2 query pool, 3 closed-loop
# order, 4 open-loop arrivals, 5 the program's seed.
PROGRAM_STREAM = 5


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One independent generator per (seed, stream): the corpus, the query
    pool and the arrivals never shift when another one changes size."""
    return np.random.default_rng([int(seed) % SEED_MOD, stream])


def program_seed(seed: int) -> int:
    """A seed for the program's own builders (some APIs want 31 bits), drawn
    from a stream of its own.  It must not be ``seed`` itself: NumPy seeds
    ``default_rng(s)`` as ``default_rng([s, 0])``, the cluster-centre stream,
    so a builder seeded with ``s`` (the RaBitQ rotation, the Vamana start
    graph) would draw the very numbers the centres were made from."""
    return int(rng_for(seed, PROGRAM_STREAM).integers(1, 2**31 - 1))


def _centres(seed: int, n: int, d: int) -> np.ndarray:
    n_clusters = max(32, n // 40)
    centres = rng_for(seed, 0).standard_normal((n_clusters, d)).astype(np.float32)
    return centres * np.float32(2.0 / np.sqrt(d))


def make_base(seed: int, n: int, d: int, noise: float = 0.3,
              block: int = 1 << 16) -> np.ndarray:
    """(n, d) float32 clustered Gaussian corpus: ~40 points per cluster, centre
    spread comparable to the intra-cluster noise.  Drawn in blocks so that the
    peak host memory stays near the corpus itself."""
    centres = _centres(seed, n, d)
    rng = rng_for(seed, 1)
    assign = rng.integers(0, centres.shape[0], size=n)
    base = np.empty((n, d), np.float32)
    for s in range(0, n, block):
        e = min(n, s + block)
        base[s:e] = rng.standard_normal((e - s, d), dtype=np.float32)
        base[s:e] *= np.float32(noise)
        base[s:e] += centres[assign[s:e]]
    return base


def make_queries(seed: int, n: int, d: int, count: int, skew: float,
                 noise: float = 0.3) -> np.ndarray:
    """(count, d) float32 queries near cluster centres chosen with Zipf
    exponent ``skew`` over clusters (0 = uniform)."""
    centres = _centres(seed, n, d)
    rng = rng_for(seed, 2)
    ranks = np.arange(1, centres.shape[0] + 1, dtype=np.float64)
    probs = ranks ** (-skew)
    probs /= probs.sum()
    pick = rng.choice(centres.shape[0], size=count, p=probs)
    q = centres[pick] + np.float32(noise) * rng.standard_normal(
        (count, d), dtype=np.float32)
    return q.astype(np.float32)


def exact_topk(base: np.ndarray, queries: np.ndarray, k: int,
               block: int = 128) -> np.ndarray:
    """Exact top-k ids by squared L2 in float32, ties broken by id."""
    n = base.shape[0]
    bn = np.einsum("ij,ij->i", base, base)
    out = np.empty((queries.shape[0], k), np.int64)
    for s in range(0, queries.shape[0], block):
        q = queries[s:s + block]
        d2 = (q * q).sum(axis=1)[:, None] - 2.0 * (q @ base.T) + bn[None, :]
        part = np.argpartition(d2, min(k, n - 1), axis=1)[:, :k]
        pd = np.take_along_axis(d2, part, axis=1)
        order = np.lexsort((part, pd), axis=1)
        out[s:s + block] = np.take_along_axis(part, order, axis=1)
    return out


def exact_dist2(base: np.ndarray, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Squared L2 distance of ``queries[i]`` to ``base[ids[i, j]]``, computed
    as a difference (no cancellation), in float64 sums of float32 inputs."""
    diff = base[ids].astype(np.float64) - queries[:, None, :].astype(np.float64)
    return np.einsum("ijk,ijk->ij", diff, diff)


def recall_at_10(ids: np.ndarray, truth: np.ndarray) -> float:
    """Mean over rows of |answer top-10 ∩ exact top-10| / 10."""
    k = 10
    hits = sum(len(set(a[:k].tolist()) & set(t[:k].tolist()))
               for a, t in zip(ids, truth))
    return hits / (truth.shape[0] * k)


class CompileCounter:
    """Counts backend compiles (persistent-cache loads included) and
    persistent-cache hits while open."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._BACKEND_COMPILE:
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
