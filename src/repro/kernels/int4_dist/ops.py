"""Public jit'd wrapper for int4_dist: padding + shape normalization."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.int4_dist import kernel as _k


def _pad_rows(x: jnp.ndarray, multiple: int):
    rem = (-x.shape[0]) % multiple
    if rem == 0:
        return x
    pad = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def nibble_halves(q: jnp.ndarray) -> jnp.ndarray:
    """(B, d) -> (2, B, d/2): the even columns (low nibbles) and the odd
    columns (high nibbles) of the query."""
    B, d = q.shape
    return q.reshape(B, d // 2, 2).transpose(2, 0, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int4_dist2(
    q: jnp.ndarray,        # (B, d)
    codes: jnp.ndarray,    # (N, d/2) uint8
    lo: jnp.ndarray,       # (N,)
    step: jnp.ndarray,     # (N,)
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Refined squared distances (B, N) from packed 4-bit codes."""
    B, N = q.shape[0], codes.shape[0]
    bq = min(_k.DEFAULT_BQ, max(8, B))
    bn = min(_k.DEFAULT_BN, max(8, N))
    qp = _pad_rows(q, bq)
    cp = _pad_rows(codes, bn)
    # pad step with 1s to keep dequant finite on padding rows
    lop = _pad_rows(lo.reshape(-1, 1), bn)
    stepp = jnp.pad(
        step.reshape(-1, 1), [(0, (-N) % bn), (0, 0)], constant_values=1.0
    )
    out = _k.int4_dist_pallas(
        nibble_halves(qp), cp, lop, stepp, bq=bq, bn=bn, interpret=interpret
    )
    return out[:B, :N]
