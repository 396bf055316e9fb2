"""Distributed vector search over a device mesh (the serving-scale plane).

The corpus is sharded across every mesh device (pod x data x model flattened
into one 'shards' view); queries are replicated; each device searches its
local shard (scan mode or graph mode); per-shard top-k merge via all_gather +
global top-k — one small collective per batch, which is why the veloann serve
cell is compute-bound in the roofline table (§Roofline).

Local ids are translated to global ids with each shard's base offset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.velo import batch_search as bs
from repro.velo import scan_search as ss
from repro.velo.index import DeviceIndex


def local_search_fn(mode: str, L: int, k: int, max_steps: int,
                    interpret: bool | None):
    if mode == "scan":
        def run(index, queries):
            ids, d2 = ss.scan_search(index, queries, k=k, rerank=L, interpret=interpret)
            return ids, d2
    elif mode == "scan_ref":
        # pure-jnp stage-1 GEMM: the dry-run lowering path (see scan_search)
        def run(index, queries):
            ids, d2 = ss.scan_search(index, queries, k=k, rerank=L, use_kernel=False)
            return ids, d2
    elif mode == "graph":
        def run(index, queries):
            ids, d2, _ = bs.batch_search(index, queries, L=L, k=k, max_steps=max_steps)
            return ids, d2
    else:
        raise ValueError(mode)
    return run


def mask_local_topk(
    ids: jnp.ndarray, d2: jnp.ndarray, offset: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Translate one shard's local top-k to global ids, masking invalid lanes.

    Under-filled shards pad their local top-k with sentinel ids (< 0).  Adding
    the shard's base offset to a sentinel produces a VALID-LOOKING global id
    (offset - 1 etc.) that can win the merged top-k — so the mask must be
    applied to the LOCAL ids, before translation: invalid lanes keep id -1 and
    get distance +inf, which loses every top-k comparison after the gather.
    """
    valid = ids >= 0
    gids = jnp.where(
        valid, ids.astype(jnp.int32) + offset.astype(jnp.int32), -1
    )
    d2 = jnp.where(valid, d2, jnp.inf)
    return gids, d2


def merge_topk(
    gids_all: jnp.ndarray, d2_all: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Global top-k over the gathered (B, S*k) candidate set."""
    neg, sel = jax.lax.top_k(-d2_all, k)
    out_ids = jnp.take_along_axis(gids_all, sel, axis=1)
    return out_ids, -neg


def make_distributed_search(
    mesh,
    axis_names: tuple[str, ...],
    mode: str = "scan",
    L: int = 64,
    k: int = 10,
    max_steps: int = 96,
    interpret: bool | None = None,
):
    """Builds a shard_map'd search: (sharded DeviceIndex, shard_offsets,
    replicated queries) -> (global ids (B, k), dist2 (B, k))."""
    local = local_search_fn(mode, L, k, max_steps, interpret)
    all_axes = axis_names

    def searcher(index: DeviceIndex, offset: jnp.ndarray, queries: jnp.ndarray):
        ids, d2 = local(index, queries)                    # local shard results
        # (B, k) global ids, invalid lanes masked BEFORE the gather
        gids_all, d2_all = mask_local_topk(ids, d2, offset)
        # merge: gather every shard's candidates, then global top-k
        for ax in all_axes:
            gids_all = jax.lax.all_gather(gids_all, ax, axis=1, tiled=True)
            d2_all = jax.lax.all_gather(d2_all, ax, axis=1, tiled=True)
        return merge_topk(gids_all, d2_all, k)

    index_specs = DeviceIndex(
        centroid=P(), rotation=P(),
        binary_codes=P(all_axes), norms=P(all_axes), ip_bar=P(all_axes),
        ext_codes=P(all_axes), ext_lo=P(all_axes), ext_step=P(all_axes),
        adjacency=P(all_axes), medoid=P(),
    )
    in_specs = (index_specs, P(all_axes), P())
    out_specs = (P(), P())

    return jax.shard_map(
        searcher, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
