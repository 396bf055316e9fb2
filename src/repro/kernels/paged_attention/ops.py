"""Public wrapper for paged decode attention."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import kernel as _k


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(
    q: jnp.ndarray,             # (B, H, Dh)
    k_pages: jnp.ndarray,       # (P, page, KVH, Dh)
    v_pages: jnp.ndarray,       # (P, page, KVH, Dh)
    block_tables: jnp.ndarray,  # (B, max_pages) int32
    context_lens: jnp.ndarray,  # (B,) int32
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    return _k.paged_attention_pallas(
        q, k_pages, v_pages,
        block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
        scale=scale, interpret=interpret,
    )
