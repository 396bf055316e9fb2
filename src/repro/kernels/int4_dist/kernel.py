"""Pallas TPU kernel: packed-4-bit dequant + squared-L2 distances.

RaBitQ level-2 refinement (paper §3.3): once a record's extended code reaches
the device tier, distances are computed against the 4-bit reconstruction.
The dequant (two nibbles per byte, per-record scale/offset) happens in VMEM
right before the MXU contraction, so HBM only ever carries d/2 bytes per
record — the same bytes the paper's SSD carries.

Layout: the low nibble of byte b is dimension 2b, the high nibble 2b+1.
Re-interleaving the two nibble planes inside the kernel would put a 2-wide
minor axis on the 128-lane axis, which pads 64x in VMEM (the compiler refused
d >= 384; 56 MB of scoped VMEM at d=960).  Instead the wrapper splits the
QUERY once into its even and odd columns, a (2, B, d/2) array, and the kernel
contracts each nibble plane against its half:

    ||q - x||^2 = |q|^2 - 2 (q_even . x_lo + q_odd . x_hi) + |x_lo|^2 + |x_hi|^2

Tiling mirrors binary_ip: BQ x BN grid cells, full d/2 in VMEM.  The TPU v5e
compiler reports a scoped VMEM allocation of 128 KB per cell at the default
BQ=BN=128 for d from 96 to 960, and 324 KB (d <= 128) to 1.23 MB (d=960) at
BN=256 (the interleaved form needed 56.4 MB at d=960, BN=256, over the 16 MB
limit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


DEFAULT_BQ = 128
DEFAULT_BN = 128

_NT = (((1,), (1,)), ((), ()))  # contract the minor axes: A @ B.T


def _int4_dist_kernel(qh_ref, codes_ref, lo_ref, step_ref, out_ref):
    q_even = qh_ref[0].astype(jnp.float32)         # (BQ, d/2)
    q_odd = qh_ref[1].astype(jnp.float32)          # (BQ, d/2)
    c = codes_ref[...].astype(jnp.int32)           # (BN, d/2)
    lo = lo_ref[...].astype(jnp.float32)           # (BN, 1)
    step = step_ref[...].astype(jnp.float32)       # (BN, 1)

    x_lo = (c & 0xF).astype(jnp.float32) * step + lo          # even dims
    x_hi = ((c >> 4) & 0xF).astype(jnp.float32) * step + lo   # odd dims

    qn = (jnp.sum(q_even * q_even, axis=1, keepdims=True)
          + jnp.sum(q_odd * q_odd, axis=1, keepdims=True))    # (BQ, 1)
    xn = jnp.sum(x_lo * x_lo, axis=1) + jnp.sum(x_hi * x_hi, axis=1)  # (BN,)
    ip = (
        jax.lax.dot_general(q_even, x_lo, _NT, preferred_element_type=jnp.float32)
        + jax.lax.dot_general(q_odd, x_hi, _NT, preferred_element_type=jnp.float32)
    )                                              # (BQ, BN)
    out_ref[...] = qn - 2.0 * ip + xn[None, :]


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def int4_dist_pallas(
    q_halves: jnp.ndarray,  # (2, B, d/2): [q[:, 0::2], q[:, 1::2]]
    codes: jnp.ndarray,     # (N, d/2) uint8
    lo: jnp.ndarray,        # (N, 1) float32
    step: jnp.ndarray,      # (N, 1) float32
    bq: int = DEFAULT_BQ,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> jnp.ndarray:
    _, B, d2 = q_halves.shape
    N, d2c = codes.shape
    assert d2 == d2c
    assert B % bq == 0 and N % bn == 0

    grid = (B // bq, N // bn)
    return pl.pallas_call(
        _int4_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((2, bq, d2), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bn, d2), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret_mode(interpret),
    )(q_halves, codes, lo, step)
