"""Batched lockstep cache-aware beam search — the coroutine model on a TPU.

The paper runs B query coroutines per core and switches on I/O.  A TPU cannot
suspend lanes, so the B-way concurrency becomes a B-row *vectorized* beam
search advanced in lockstep by `jax.lax.scan` (DESIGN.md §2 adaptation 2):

  * one scan step = every query expands its best unvisited candidate;
  * neighbor gathers for the whole batch coalesce into one HBM gather —
    the io_uring batched-submission analogue;
  * level-1 (binary) estimates steer the beam; level-2 (int4) refinement is
    applied once at the end to the surviving beam (TPU-natural: one batched
    rerank instead of per-step scalar refinement; recall parity with the host
    plane is asserted in tests/test_velo_device.py).

Everything here is jit/pjit-compatible: static shapes, no host sync inside
the scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.velo.index import DeviceIndex, refine_rows

INF = jnp.float32(3e38)


def _prepare_queries(index: DeviceIndex, q: jnp.ndarray):
    qr = (q - index.centroid[None, :]) @ index.rotation.T
    qnorm = jnp.linalg.norm(qr, axis=1, keepdims=True)
    qunit = qr / jnp.maximum(qnorm, 1e-12)
    return qr, qnorm, qunit


def _estimate(index: DeviceIndex, ids: jnp.ndarray, qunit: jnp.ndarray, qnorm: jnp.ndarray):
    """Level-1 estimates for gathered ids: ids (B, M), qunit (B, d) -> (B, M)."""
    d = index.dim
    codes = index.binary_codes[ids]                      # (B, M, d/8)
    c = codes.astype(jnp.int32)
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (c[..., None] >> shifts) & 1                  # (B, M, d/8, 8)
    signs = (2 * bits - 1).reshape(*ids.shape, d).astype(jnp.float32)
    g = jnp.einsum("bmd,bd->bm", signs, qunit) / jnp.sqrt(jnp.float32(d))
    ipb = jnp.maximum(index.ip_bar[ids], 1e-6)
    est_cos = jnp.clip(g / ipb, -1.0, 1.0)
    nr = index.norms[ids]
    return qnorm**2 + nr**2 - 2.0 * qnorm * nr * est_cos


def _merge_and_trim(ids, dist, visited, new_ids, new_dist, L, sentinel):
    """Concat beams with expansions, dedupe by id, keep top-L by distance."""
    all_ids = jnp.concatenate([ids, new_ids], axis=1)
    all_dist = jnp.concatenate([dist, new_dist], axis=1)
    all_vis = jnp.concatenate([visited, jnp.zeros_like(new_ids, dtype=bool)], axis=1)

    # dedupe: sort by id; runs of equal REAL ids have length <= 2 here (beam
    # rows are unique post-trim, adjacency rows are unique), so one
    # neighbor-pair aggregation suffices: the first copy takes min(dist) and
    # OR(visited), the second copy is killed.
    order = jnp.argsort(all_ids, axis=1)
    sid = jnp.take_along_axis(all_ids, order, axis=1)
    sdist = jnp.take_along_axis(all_dist, order, axis=1)
    svis = jnp.take_along_axis(all_vis, order, axis=1)
    eq = sid[:, 1:] == sid[:, :-1]
    zeros = jnp.zeros_like(sid[:, :1], dtype=bool)
    nxt_same = jnp.concatenate([eq, zeros], axis=1)   # next element is my dup
    prv_same = jnp.concatenate([zeros, eq], axis=1)   # I am the dup copy
    sdist_nxt = jnp.roll(sdist, -1, axis=1)
    svis_nxt = jnp.roll(svis, -1, axis=1)
    sdist = jnp.where(nxt_same, jnp.minimum(sdist, sdist_nxt), sdist)
    svis = jnp.where(nxt_same, svis | svis_nxt, svis)
    # a killed copy must ALSO forfeit its id: on an underfull beam the
    # (INF, visited) tail survives the trim, and a ghost that kept a real id
    # would pair with that id's live copy in a LATER merge — the OR(visited)
    # aggregation would then falsely mark the live candidate visited (and a
    # 3-long run would break the pairwise-dedupe assumption above)
    sid = jnp.where(prv_same, sentinel, sid)
    sdist = jnp.where(prv_same, INF, sdist)
    svis = jnp.where(prv_same, True, svis)

    order2 = jnp.argsort(sdist, axis=1)[:, :L]
    ids = jnp.take_along_axis(sid, order2, axis=1)
    dist = jnp.take_along_axis(sdist, order2, axis=1)
    visited = jnp.take_along_axis(svis, order2, axis=1)
    visited = visited | (dist >= INF)
    return ids, dist, visited


@functools.partial(jax.jit, static_argnames=("L", "k", "max_steps"))
def batch_search(
    index: DeviceIndex,
    queries: jnp.ndarray,    # (B, d)
    L: int = 64,
    k: int = 10,
    max_steps: int = 96,
):
    """Returns (ids (B, k) int32, dist2 (B, k) f32, steps_executed (B,))."""
    B, d = queries.shape
    qr, qnorm, qunit = _prepare_queries(index, queries)
    n = index.n

    ids = jnp.full((B, L), n, dtype=jnp.int32)           # sentinel-filled
    dist = jnp.full((B, L), INF, dtype=jnp.float32)
    visited = jnp.ones((B, L), dtype=bool)

    medoid = jnp.full((B, 1), index.medoid, dtype=jnp.int32)
    med_est = _estimate(index, medoid, qunit, qnorm)
    ids = ids.at[:, 0].set(medoid[:, 0])
    dist = dist.at[:, 0].set(med_est[:, 0])
    visited = visited.at[:, 0].set(False)

    # global seen-set: one bit per vertex per query (the lockstep analogue of
    # the host's per-coroutine `seen`); sentinel row pre-marked.
    seen = jnp.zeros((B, n + 1), dtype=bool).at[:, -1].set(True)
    seen = seen.at[jnp.arange(B), medoid[:, 0]].set(True)

    def step(carry, _):
        ids, dist, visited, seen, steps = carry
        masked = jnp.where(visited, INF, dist)
        bi = jnp.argmin(masked, axis=1)                   # (B,)
        best = jnp.take_along_axis(masked, bi[:, None], axis=1)[:, 0]
        active = best < INF
        cur = jnp.take_along_axis(ids, bi[:, None], axis=1)[:, 0]
        cur = jnp.where(active, cur, n)
        visited = jnp.where(
            active[:, None],
            visited.at[jnp.arange(ids.shape[0]), bi].set(True),
            visited,
        )

        neigh = index.adjacency[cur]                      # (B, R)
        fresh = ~jnp.take_along_axis(seen, neigh, axis=1)  # (B, R)
        est = _estimate(index, neigh, qunit, qnorm)
        est = jnp.where(fresh & active[:, None], est, INF)
        seen = seen.at[jnp.arange(ids.shape[0])[:, None], neigh].set(True)

        ids, dist, visited = _merge_and_trim(
            ids, dist, visited, neigh, est, ids.shape[1], n
        )
        return (ids, dist, visited, seen, steps + active.astype(jnp.int32)), None

    (ids, dist, visited, seen, steps), _ = jax.lax.scan(
        step, (ids, dist, visited, seen, jnp.zeros(B, jnp.int32)), None,
        length=max_steps,
    )

    # final rerank: int4 refinement of the surviving beam, take top-k
    refined = refine_rows(index, ids, qr)
    refined = jnp.where(dist >= INF, INF, refined)
    order = jnp.argsort(refined, axis=1)[:, :k]
    top_ids = jnp.take_along_axis(ids, order, axis=1)
    top_d2 = jnp.take_along_axis(refined, order, axis=1)
    return top_ids, top_d2, steps
