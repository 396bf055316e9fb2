"""DeviceIndex: the compressed VeloANN index as a pytree of device arrays.

Shares the exact artifact format with the host plane (core.quant /
core.vamana): binary codes + norms + ip_bar steer traversal, 4-bit ext codes
refine, padded adjacency drives graph gathers.  A sentinel row is appended so
padding ids (-1 -> n) gather safely and estimate to +inf.

Level 2 is one 32-bit row table, ``ext_rows`` (n+1, W) uint32: row i holds
its d/2 bytes of nibble codes as d/8 little-endian words, then the f32 bits
of its ``ext_lo`` and ``ext_step``, then zeros.  A candidate's refine is one
row gather (``refine_rows``): a row of W words is whole lanes of the TPU's
native (8, 128) 32-bit tile, which the gather reads in place, where an
8-bit table would be relaid out on every call.
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
    ext_rows: jnp.ndarray       # (n+1, ext_row_words(d)) uint32 — level 2
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


def ext_row_words(d: int) -> int:
    """Words of an ``ext_rows`` row at width ``d``: d/8 of codes, lo and
    step, padded to whole 128-lane rows where that is more than 64 words
    (one candidate = one lane row of a native tile), else to a multiple of
    8 (at d=96, 14 -> 16 words, not 128)."""
    if d % 8:
        raise ValueError(f"ext_rows packs 8 nibbles a word: d={d} is not a multiple of 8")
    w = d // 8 + 2
    lanes = 128 if w > 64 else 8
    return -(-w // lanes) * lanes


def pack_ext_rows(codes: np.ndarray, lo: np.ndarray, step: np.ndarray) -> np.ndarray:
    """(m, d/2) uint8 nibble codes, (m,) f32 lo and step -> (m, W) uint32
    rows in the ``ext_rows`` layout, by NumPy views of the same bytes."""
    m, half = codes.shape
    cw = half // 4
    rows = np.zeros((m, ext_row_words(2 * half)), np.uint32)
    rows[:, :cw] = np.ascontiguousarray(codes, np.uint8).view("<u4")
    rows[:, cw] = np.asarray(lo, np.float32).view(np.uint32)
    rows[:, cw + 1] = np.asarray(step, np.float32).view(np.uint32)
    return rows


def unpack_ext_rows(rows: jnp.ndarray, d: int):
    """Gathered ``ext_rows`` rows (..., W) -> (codes (..., d) f32, lo (...)
    f32, step (...) f32).  Word j's nibble t (bits 4t..4t+3) is dim 8j + t:
    a byte's low nibble is dim 2b and its high nibble 2b + 1, as
    ``core.quant.pack_nibbles`` packs them."""
    cw = d // 8
    shifts = jnp.arange(0, 32, 4, dtype=jnp.uint32)
    nib = (rows[..., :cw, None] >> shifts) & jnp.uint32(0xF)  # (..., d/8, 8)
    codes = nib.astype(jnp.float32).reshape(*rows.shape[:-1], d)
    lo = jax.lax.bitcast_convert_type(rows[..., cw], jnp.float32)
    step = jax.lax.bitcast_convert_type(rows[..., cw + 1], jnp.float32)
    return codes, lo, step


def refine_rows(index: DeviceIndex, ids: jnp.ndarray, qr: jnp.ndarray) -> jnp.ndarray:
    """Level-2 int4 squared distances of gathered ids: ids (B, M), rotated
    queries qr (B, d) -> (B, M) f32, from one gather of ``ext_rows``.
    x = code * step + lo, then the sum of (qr - x)^2, in f32."""
    codes, lo, step = unpack_ext_rows(index.ext_rows[ids], index.dim)
    x = codes * step[..., None] + lo[..., None]
    diff = qr[:, None, :] - x
    return jnp.einsum("bmd,bmd->bm", diff, diff)


def host_arrays(qb, graph=None) -> dict[str, np.ndarray]:
    """The fields of a ``DeviceIndex`` as host arrays, sentinel row appended,
    from host-plane artifacts (QuantizedBase + VamanaGraph).  Scan mode reads
    no adjacency: with ``graph=None`` every row gets one sentinel neighbour
    and the medoid is row 0."""
    if qb.ext_bits != 4:
        raise ValueError(f"DeviceIndex refines 4-bit level-2 codes, not {qb.ext_bits}-bit")
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
        ext_rows=pack_ext_rows(
            np.concatenate([qb.ext_codes, np.zeros((1, qb.ext_codes.shape[1]), np.uint8)]),
            np.concatenate([qb.ext_lo, [0.0]]), np.concatenate([qb.ext_step, [1.0]])),
        adjacency=np.concatenate([adj, sent_adj]),
        medoid=np.asarray(medoid, dtype=np.int32),
    )


def from_host(qb, graph=None) -> DeviceIndex:
    """Build the device pytree from host-plane artifacts (``host_arrays``) on
    the default device."""
    return DeviceIndex(**{f: jnp.asarray(v) for f, v in host_arrays(qb, graph).items()})


def synthetic_specs(n: int, d: int, R: int):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    f32, u8, u32, i32 = jnp.float32, jnp.uint8, jnp.uint32, jnp.int32
    S = jax.ShapeDtypeStruct
    return DeviceIndex(
        centroid=S((d,), f32),
        rotation=S((d, d), f32),
        binary_codes=S((n + 1, d // 8), u8),
        norms=S((n + 1,), f32),
        ip_bar=S((n + 1,), f32),
        ext_rows=S((n + 1, ext_row_words(d)), u32),
        adjacency=S((n + 1, R), i32),
        medoid=S((), i32),
    )
