"""DeviceIndex: the compressed VeloANN index as a pytree of device arrays.

Shares the exact artifact format with the host plane (core.quant /
core.vamana): binary codes + norms + ip_bar steer traversal, 4-bit ext codes
refine, padded adjacency drives graph gathers.  A sentinel row is appended so
padding ids (-1 -> n) gather safely and estimate to +inf.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeviceIndex:
    centroid: jnp.ndarray       # (d,)
    rotation: jnp.ndarray       # (d, d)
    binary_codes: jnp.ndarray   # (n+1, d/8) uint8
    norms: jnp.ndarray          # (n+1,)  — sentinel row: +inf
    ip_bar: jnp.ndarray         # (n+1,)
    ext_codes: jnp.ndarray      # (n+1, d/2) uint8
    ext_lo: jnp.ndarray         # (n+1,)
    ext_step: jnp.ndarray       # (n+1,)
    adjacency: jnp.ndarray      # (n+1, R) int32, -1 padding replaced by n
    medoid: jnp.ndarray         # () int32

    @property
    def n(self) -> int:
        return self.binary_codes.shape[0] - 1

    @property
    def dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def R(self) -> int:
        return self.adjacency.shape[1]


def host_arrays(qb, graph=None) -> dict[str, np.ndarray]:
    """The fields of a ``DeviceIndex`` as host arrays, sentinel row appended,
    from host-plane artifacts (QuantizedBase + VamanaGraph).  Scan mode reads
    no adjacency: with ``graph=None`` every row gets one sentinel neighbour
    and the medoid is row 0."""
    n = qb.norms.shape[0]
    if graph is None:
        adj, medoid = np.full((n, 1), n, dtype=np.int32), 0
    else:
        adj, medoid = graph.adjacency.copy(), graph.medoid
        adj[adj < 0] = n  # sentinel
    sent_adj = np.full((1, adj.shape[1]), n, dtype=np.int32)
    big = np.float32(1e30)
    return dict(
        centroid=qb.centroid,
        rotation=qb.rotation,
        binary_codes=np.concatenate(
            [qb.binary_codes, np.zeros((1, qb.binary_codes.shape[1]), np.uint8)]),
        norms=np.concatenate([qb.norms, [big]]),
        ip_bar=np.concatenate([qb.ip_bar, [1.0]]).astype(np.float32),
        ext_codes=np.concatenate(
            [qb.ext_codes, np.zeros((1, qb.ext_codes.shape[1]), np.uint8)]),
        ext_lo=np.concatenate([qb.ext_lo, [0.0]]).astype(np.float32),
        ext_step=np.concatenate([qb.ext_step, [1.0]]).astype(np.float32),
        adjacency=np.concatenate([adj, sent_adj]),
        medoid=np.asarray(medoid, dtype=np.int32),
    )


def from_host(qb, graph=None) -> DeviceIndex:
    """Build the device pytree from host-plane artifacts (``host_arrays``) on
    the default device."""
    return DeviceIndex(**{f: jnp.asarray(v) for f, v in host_arrays(qb, graph).items()})


def synthetic_specs(n: int, d: int, R: int):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    f32, u8, i32 = jnp.float32, jnp.uint8, jnp.int32
    S = jax.ShapeDtypeStruct
    return DeviceIndex(
        centroid=S((d,), f32),
        rotation=S((d, d), f32),
        binary_codes=S((n + 1, d // 8), u8),
        norms=S((n + 1,), f32),
        ip_bar=S((n + 1,), f32),
        ext_codes=S((n + 1, d // 2), u8),
        ext_lo=S((n + 1,), f32),
        ext_step=S((n + 1,), f32),
        adjacency=S((n + 1, R), i32),
        medoid=S((), i32),
    )
