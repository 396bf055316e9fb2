"""Distributed vector search over a device mesh (the serving-scale plane).

The corpus is sharded across every mesh device (pod x data x model flattened
into one 'shards' view); queries are replicated; each device searches its
local shard (scan mode or graph mode); per-shard top-k merge via all_gather +
global top-k — one small collective per batch, which is why the veloann serve
cell is compute-bound in the roofline table (§Roofline).

Local ids are translated to global ids with each shard's base offset.

``ShardedScan`` is the scan mode as one call: it splits a host-encoded index
into equal shards, one per device, each with its own sentinel row
(``shard_from_host``), and runs the jitted program ``sharded_scan`` over them.
``dist_search_ref`` is its plain NumPy reference.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.spans import span
from repro.velo import batch_search as bs
from repro.velo import scan_search as ss
from repro.velo.index import DeviceIndex, host_arrays

PROGRAM = "sharded_scan"   # the jitted program: module events jit_sharded_scan(...)
MERGE_SCOPE = "velo.shard.merge"
AXIS = "shards"            # the sharded scan's one mesh axis

# DeviceIndex fields with one row per corpus row (and sentinel), split across shards
ROW_FIELDS = ("binary_codes", "norms", "ip_bar", "ext_rows")
# QuantizedBase fields with one entry per corpus row: shard_rows cuts these
QB_ROW_FIELDS = ("binary_codes", "norms", "ip_bar", "ext_codes", "ext_lo", "ext_step")
# DeviceIndex fields every device holds whole
REPLICATED = ("centroid", "rotation", "medoid")


def local_search_fn(mode: str, L: int, k: int, max_steps: int,
                    interpret: bool | None, chunk: int = ss.DEFAULT_CHUNK,
                    use_kernel: bool = True):
    if mode == "scan":
        def run(index, queries):
            return ss.scan_search(index, queries, k=k, rerank=L, interpret=interpret,
                                  use_kernel=use_kernel, chunk=chunk)
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
    chunk: int = ss.DEFAULT_CHUNK,
    use_kernel: bool = True,
):
    """Builds a shard_map'd search: (sharded DeviceIndex, shard_offsets,
    replicated queries) -> (global ids (B, k), dist2 (B, k)).  ``chunk`` and
    ``use_kernel`` go to each shard's ``scan_search``."""
    local = local_search_fn(mode, L, k, max_steps, interpret, chunk, use_kernel)
    all_axes = axis_names

    def searcher(index: DeviceIndex, offset: jnp.ndarray, queries: jnp.ndarray):
        ids, d2 = local(index, queries)                    # local shard results
        with jax.named_scope(MERGE_SCOPE):
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
        ext_rows=P(all_axes), adjacency=P(all_axes), medoid=P(),
    )
    in_specs = (index_specs, P(all_axes), P())
    out_specs = (P(), P())

    return jax.shard_map(
        searcher, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def sharded_scan_program(mesh, k: int = 10, rerank: int = 512,
                         chunk: int = ss.DEFAULT_CHUNK, use_kernel: bool = True,
                         interpret: bool | None = None):
    """The jitted scan-mode search over ``mesh``'s ``AXIS``, named
    ``PROGRAM`` so that a trace's module events read ``jit_sharded_scan``."""
    search = make_distributed_search(mesh, (AXIS,), mode="scan", L=rerank, k=k,
                                     interpret=interpret, chunk=chunk,
                                     use_kernel=use_kernel)

    def sharded_scan(index, offsets, queries):
        return search(index, offsets, queries)

    return jax.jit(sharded_scan)


# ------------------------------------------------------------ placement


def shard_rows(qb, shards: int) -> list:
    """``qb`` (a host ``QuantizedBase``) cut into ``shards`` equal blocks of
    rows, in order; centroid and rotation stay the whole corpus's, so every
    shard's distances are on one scale."""
    n = qb.norms.shape[0]
    if n % shards:
        raise ValueError(f"corpus n={n} does not split evenly over {shards} shards")
    per = n // shards
    return [dataclasses.replace(qb, **{f: getattr(qb, f)[s * per:(s + 1) * per]
                                       for f in QB_ROW_FIELDS})
            for s in range(shards)]


def host_shards(qb, shards: int) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """The sharded index on the host: each row field the shards' blocks one
    after another, each block with its own sentinel row (``host_arrays``),
    so that an even split of (n + shards) rows gives each device one shard;
    and each shard's first global id."""
    blocks = [host_arrays(part) for part in shard_rows(qb, shards)]
    out = {f: blocks[0][f] if f in REPLICATED else np.concatenate([b[f] for b in blocks])
           for f in blocks[0]}
    per = qb.norms.shape[0] // shards
    return out, np.arange(shards, dtype=np.int32) * per


def place(host: dict[str, np.ndarray], offsets: np.ndarray,
          mesh) -> tuple[DeviceIndex, jax.Array]:
    """``host_shards``' arrays on ``mesh``: row fields and offsets split
    over ``AXIS``, the rest replicated on every device."""
    split, whole = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())
    index = DeviceIndex(**{f: jax.device_put(v, whole if f in REPLICATED else split)
                           for f, v in host.items()})
    return index, jax.device_put(offsets, split)


def shard_from_host(qb, mesh) -> tuple[DeviceIndex, jax.Array]:
    """A host-encoded index split evenly over ``mesh``'s ``AXIS``, one shard
    per device with its own sentinel row, and the shards' global offsets."""
    return place(*host_shards(qb, mesh.shape[AXIS]), mesh)


class ShardedScan:
    """The scan mode over ``devices`` as one call: ``qb``'s rows in equal
    shards, one per device; ``search(queries)`` replicates a (B, d) batch to
    every device, scans each shard with ``scan_search`` and merges the
    per-shard top-k into global ids and squared distances (B, k).

    Each batch size compiles once, ahead of time, on its first call; the
    compiled program's HLO text (``hlo_text``) names the trace's operations.
    """

    def __init__(self, qb, devices, k: int = 10, rerank: int = 512,
                 chunk: int = ss.DEFAULT_CHUNK, use_kernel: bool = True):
        self.mesh = Mesh(np.asarray(devices), (AXIS,))
        self.shards = len(devices)
        self.index, self.offsets = shard_from_host(qb, self.mesh)
        self._whole = NamedSharding(self.mesh, P())
        self._program = sharded_scan_program(self.mesh, k, rerank, chunk, use_kernel)
        self._compiled: dict[int, object] = {}

    def put(self, queries) -> jax.Array:
        """The batch replicated on every device (a no-op where it is)."""
        return jax.device_put(queries, self._whole)

    def _compiled_for(self, batch: int):
        if batch not in self._compiled:
            q = jax.ShapeDtypeStruct((batch, self.index.dim), jnp.float32,
                                     sharding=self._whole)
            self._compiled[batch] = self._program.lower(
                self.index, self.offsets, q).compile()
        return self._compiled[batch]

    def search(self, queries) -> tuple[jax.Array, jax.Array]:
        with span("velo.shard.call", rows=queries.shape[0], shards=self.shards):
            return self._compiled_for(queries.shape[0])(
                self.index, self.offsets, self.put(queries))

    def hlo_text(self, batch: int) -> str:
        return self._compiled_for(batch).as_text()
