"""Plain reference of the sharded scan (``dist_search.ShardedScan``).

NumPy float32, no kernels, no JAX.  The corpus's RaBitQ codes are cut into
``shards`` equal blocks of rows, in order.  For each query and each shard:

1. the level-1 estimate of every row of the shard
   (``RabitQuantizer.estimate_batch``'s formula);
2. the shard's top-C by estimate, ties to the lower id;
3. the int4 squared distance of those C candidates
   (``RabitQuantizer.refine_batch``);
4. the candidates' local ids plus the shard's offset.

Then the global top-k of the shards' candidates by (int4 distance, id).

One departure from the device: the device computes the level-1 estimate in
bf16, so its top-C may differ from this one's at a shard's C-th place, where
estimates lie within bf16's rounding of each other.  The int4 distances of
the ids it returns do not depend on that choice.
"""

from __future__ import annotations

import numpy as np

from repro.core.quant import PreparedQuery, RabitQuantizer, unpack_bits


def shard_offsets(n: int, shards: int) -> np.ndarray:
    if n % shards:
        raise ValueError(f"corpus n={n} does not split evenly over {shards} shards")
    return np.arange(shards, dtype=np.int64) * (n // shards)


def estimates(qb, pqs: list[PreparedQuery], rows: slice) -> np.ndarray:
    """(len(pqs), rows) level-1 estimates: ``estimate_batch`` for each query
    over ``qb``'s rows ``rows``, with the rows' signs unpacked once."""
    d = qb.dim
    signs = 2.0 * unpack_bits(qb.binary_codes[rows], d).astype(np.float32) - 1.0
    qunit = np.stack([pq.qunit for pq in pqs])                       # (Q, d)
    qnorm = np.asarray([pq.qnorm for pq in pqs])[:, None]
    norms, ip_bar = qb.norms[rows][None, :], qb.ip_bar[rows][None, :]
    g = (qunit @ signs.T) / np.sqrt(d)
    est_cos = np.clip(g / np.maximum(ip_bar, 1e-6), -1.0, 1.0)
    out = qnorm**2 + norms**2 - 2.0 * qnorm * norms * est_cos
    return out.astype(np.float32, copy=False)


def smallest_ids(values: np.ndarray, c: int) -> np.ndarray:
    """Positions of the ``c`` smallest of ``values``, by (value, position)."""
    c = min(c, len(values))
    kth = np.partition(values, c - 1)[c - 1]
    cand = np.flatnonzero(values <= kth)              # every tie at the c-th value
    return cand[np.lexsort((cand, values[cand]))][:c]


def search(qb, queries: np.ndarray, shards: int, k: int = 10, rerank: int = 512,
           block: int = 256) -> tuple[np.ndarray, np.ndarray]:
    """(global ids (Q, k) int64, int4 squared distances (Q, k) float32) of the
    sharded scan over ``qb``; queries are taken ``block`` at a time."""
    n = qb.norms.shape[0]
    offsets = shard_offsets(n, shards)
    per = n // shards
    out_i = np.empty((len(queries), k), np.int64)
    out_d = np.empty((len(queries), k), np.float32)
    for s0 in range(0, len(queries), block):
        pqs = [RabitQuantizer.prepare_query(qb, q) for q in queries[s0:s0 + block]]
        cand_i = [[] for _ in pqs]
        cand_d = [[] for _ in pqs]
        for off in offsets:
            est = estimates(qb, pqs, slice(off, off + per))
            for j, pq in enumerate(pqs):
                ids = off + smallest_ids(est[j], rerank)
                cand_i[j].append(ids)
                cand_d[j].append(RabitQuantizer.refine_batch(
                    qb, pq, qb.ext_codes[ids], qb.ext_lo[ids], qb.ext_step[ids]))
        for j in range(len(pqs)):
            ids, d2 = np.concatenate(cand_i[j]), np.concatenate(cand_d[j])
            top = np.lexsort((ids, d2))[:k]
            out_i[s0 + j], out_d[s0 + j] = ids[top], d2[top]
    return out_i, out_d


def int4_dist2(qb, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """(Q, m) int4 squared distance of ``queries[i]`` to row ``ids[i, j]``
    (``refine_batch``); NaN where an id lies outside the corpus."""
    n = qb.norms.shape[0]
    out = np.full(ids.shape, np.nan, np.float32)
    for i, q in enumerate(queries):
        ok = (ids[i] >= 0) & (ids[i] < n)
        r = ids[i][ok]
        out[i, ok] = RabitQuantizer.refine_batch(
            qb, RabitQuantizer.prepare_query(qb, q), qb.ext_codes[r], qb.ext_lo[r],
            qb.ext_step[r])
    return out


def topk_agree(ids_a, d_a, ids_b, d_b, rtol=1e-5, atol=1e-5) -> bool:
    """Row-wise equal top-k ids, except where the two differ only among
    candidates at exactly tied distances."""
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
