"""Distributed vector search over a device mesh (device plane).

Shards a corpus over every device JAX has, runs the kernel-backed two-stage
compressed scan per shard under shard_map, merges with a distributed top-k —
the same program the 512-chip veloann dry-run cell lowers.

  PYTHONPATH=src python examples/distributed_search.py        # the chips
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      PYTHONPATH=src python examples/distributed_search.py    # 4 CPU devices
"""

import sys
import dataclasses

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dataset, vamana
from repro.core.dataset import recall_at_k
from repro.core.quant import RabitQuantizer
from repro.velo import dist_search
from repro.velo.index import DeviceIndex, from_host


def main():
    n_shards = jax.device_count()
    ds = dataset.make_dataset(n=512 * n_shards, d=64, n_queries=64, k=10, seed=3)
    per = ds.n // n_shards
    qb = RabitQuantizer(64, seed=0).fit_encode(ds.base)

    # per-shard local graphs (standard sharded-ANN construction)
    parts = []
    for s in range(n_shards):
        lo, hi = s * per, (s + 1) * per
        g = vamana.build_vamana(ds.base[lo:hi], R=12, L=24, seed=s, two_pass=False)
        sub = dataclasses.replace(
            qb,
            binary_codes=qb.binary_codes[lo:hi], norms=qb.norms[lo:hi],
            ip_bar=qb.ip_bar[lo:hi], ext_codes=qb.ext_codes[lo:hi],
            ext_lo=qb.ext_lo[lo:hi], ext_step=qb.ext_step[lo:hi],
        )
        parts.append(from_host(sub, g))

    def cat(field):
        return jnp.concatenate([getattr(p, field) for p in parts], axis=0)

    index = DeviceIndex(
        centroid=parts[0].centroid, rotation=parts[0].rotation,
        binary_codes=cat("binary_codes"), norms=cat("norms"),
        ip_bar=cat("ip_bar"), ext_codes=cat("ext_codes"),
        ext_lo=cat("ext_lo"), ext_step=cat("ext_step"),
        adjacency=cat("adjacency"), medoid=parts[0].medoid,
    )
    offsets = jnp.asarray(np.arange(n_shards) * per, jnp.int32)

    mesh = jax.make_mesh((n_shards,), ("shards",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    search = dist_search.make_distributed_search(
        mesh, ("shards",), mode="scan", L=64, k=10
    )
    ids, d2 = search(index, offsets, jnp.asarray(ds.queries))
    rec = recall_at_k(np.asarray(ids), ds.groundtruth, 10)
    print(f"devices={n_shards} corpus={ds.n} sharded search recall@10={rec:.3f}")
    assert rec > 0.8
    print("OK")


if __name__ == "__main__":
    main()
