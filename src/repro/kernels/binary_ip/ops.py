"""Public jit'd wrapper for the binary_ip kernel: padding + estimate assembly."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.binary_ip import kernel as _k


def _pad_to(x: jnp.ndarray, axis: int, multiple: int):
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), size


def bit_planes(q: jnp.ndarray) -> jnp.ndarray:
    """(B, d) -> (8, B, d/8): plane j holds columns j, 8+j, 16+j, ... — the
    dimensions stored in bit j of each code byte (little-endian packbits)."""
    B, d = q.shape
    return q.reshape(B, d // 8, 8).transpose(2, 0, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def binary_ip(
    q: jnp.ndarray, codes: jnp.ndarray, interpret: bool | None = None
) -> jnp.ndarray:
    """<q_b, sign_n> (B, N) via the Pallas kernel, any B/N (auto-padded)."""
    bq = min(_k.DEFAULT_BQ, max(8, q.shape[0]))
    bn = min(_k.DEFAULT_BN, max(8, codes.shape[0]))
    qp, B = _pad_to(q, 0, bq)
    cp, N = _pad_to(codes, 0, bn)
    out = _k.binary_ip_pallas(bit_planes(qp), cp, bq=bq, bn=bn, interpret=interpret)
    return out[:B, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def estimate_dist2(
    q: jnp.ndarray,        # (B, d) rotated centered queries
    codes: jnp.ndarray,    # (N, d/8) uint8
    norms: jnp.ndarray,    # (N,)
    ip_bar: jnp.ndarray,   # (N,)
    interpret: bool | None = None,
) -> jnp.ndarray:
    """RaBitQ level-1 estimated squared distances (B, N).

    The GEMM runs in the kernel; the cheap per-element estimator assembly
    (norm corrections) is left to XLA fusion.
    """
    d = q.shape[1]
    qnorm = jnp.linalg.norm(q, axis=1, keepdims=True)
    qunit = q / jnp.maximum(qnorm, 1e-12)
    g = binary_ip(qunit, codes, interpret=interpret) / jnp.sqrt(jnp.float32(d))
    est_cos = jnp.clip(g / jnp.maximum(ip_bar[None, :], 1e-6), -1.0, 1.0)
    return qnorm**2 + norms[None, :] ** 2 - 2.0 * qnorm * norms[None, :] * est_cos
