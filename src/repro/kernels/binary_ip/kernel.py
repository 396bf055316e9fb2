"""Pallas TPU kernel: query x packed-1-bit-code inner products as a sign GEMM.

Hardware adaptation (DESIGN.md §2): on CPUs RaBitQ's level-1 distance is a
popcount-Hamming loop; the TPU has no popcount but has a 128x128 systolic MXU.
We therefore unpack the bit codes to {-1,+1} *inside VMEM* and issue dense
GEMMs — d/8 bytes per code row feed 2d flops.

Layout: bit j of code byte b is dimension 8b+j.  Unpacking that interleave
inside the kernel would put an 8-wide minor axis on the 128-lane axis, which
pads 16x in VMEM (the compiler refused d=960 at 18.7 MB of scoped VMEM).
Instead the wrapper de-interleaves the QUERY once — plane j holds the query's
columns j, 8+j, 16+j, ... as a (8, B, d/8) array — and the kernel sums eight
bit-plane GEMMs of width d/8, each against ``(codes >> j) & 1`` mapped to ±1.
No reshape of the code tile ever happens.

Tiling: queries (BQ=128 rows) x codes (BN=256 rows) per grid cell; the whole
d/8-byte code row and the 8 query planes live in VMEM.  The TPU v5e compiler
reports a scoped VMEM allocation of 180 KB per cell at every d from 96 to 960
(the lane-interleaved unpack it replaces needed 18.7 MB at d=960, over the
16 MB limit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


DEFAULT_BQ = 128
DEFAULT_BN = 256


def _binary_ip_kernel(qp_ref, codes_ref, out_ref):
    c = codes_ref[...].astype(jnp.int32)           # (BN, d/8) u8 -> i32
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(8):
        signs = (2 * ((c >> j) & 1) - 1).astype(jnp.float32)   # (BN, d/8)
        acc += jax.lax.dot_general(
            qp_ref[j].astype(jnp.float32),         # (BQ, d/8) plane j
            signs,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def binary_ip_pallas(
    q_planes: jnp.ndarray,  # (8, B, d/8) float: plane j = q[:, j::8]
    codes: jnp.ndarray,     # (N, d/8) uint8
    bq: int = DEFAULT_BQ,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> jnp.ndarray:
    _, B, d8 = q_planes.shape
    N, d8c = codes.shape
    assert d8 == d8c
    assert B % bq == 0 and N % bn == 0, "caller (ops.py) pads to tile multiples"

    grid = (B // bq, N // bn)
    return pl.pallas_call(
        _binary_ip_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((8, bq, d8), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bn, d8), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret_mode(interpret),
    )(q_planes, codes)
