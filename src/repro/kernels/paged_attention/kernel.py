"""Pallas TPU kernel: decode attention through a record-level KV block table.

This is the paper's §3.2 'record mapping array' idea applied to the KV cache
(DESIGN.md §Arch-applicability): the block table is the indirection array,
KV pages are the records, and the scalar-prefetch index_map *is* the hybrid
pointer dereference — the page id is read from SMEM before the DMA for the
corresponding KV tile is issued, so the gather never materializes a dense
(B, S, H, Dh) KV in HBM.

grid = (B, H, max_pages); the page axis is innermost/sequential, carrying the
online-softmax state in VMEM scratch.  Pages beyond a sequence's context
length are masked (their DMA still runs — TPU grids are static — but a real
deployment sizes max_pages to the batch's max context, exactly like vLLM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


NEG_INF = -1e30


def _paged_kernel(
    # scalar-prefetch operands
    block_tables_ref,           # (B, max_pages) int32 in SMEM
    context_lens_ref,           # (B,) int32 in SMEM
    # array operands
    q_ref,                      # (1, 1, Dh)
    k_ref,                      # (1, page, 1, Dh) — page selected by index_map
    v_ref,
    o_ref,                      # (1, 1, Dh)
    m_scratch, l_scratch, acc_scratch,
    *, scale: float, page: int,
):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q = q_ref[0, 0].astype(jnp.float32)            # (Dh,)
    k = k_ref[0, :, 0].astype(jnp.float32)         # (page, Dh)
    v = v_ref[0, :, 0].astype(jnp.float32)         # (page, Dh)

    logits = (k @ q) * scale                        # (page,)
    pos = pi * page + jax.lax.iota(jnp.int32, page)
    valid = pos < context_lens_ref[b]
    logits = jnp.where(valid, logits, NEG_INF)
    logits = logits[None, :]                        # (1, page)

    m_prev = m_scratch[...]
    l_prev = l_scratch[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    p = jnp.exp(logits - m_new)                     # (1, page)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scratch[...] = acc_scratch[...] * alpha + p @ v  # (1, Dh)
    m_scratch[...] = m_new
    l_scratch[...] = l_new

    @pl.when(pi == np_ - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scratch[...] / jnp.maximum(l_scratch[...], 1e-30)
        )[0].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret")
)
def paged_attention_pallas(
    q: jnp.ndarray,             # (B, H, Dh)
    k_pages: jnp.ndarray,       # (P, page, KVH, Dh)
    v_pages: jnp.ndarray,       # (P, page, KVH, Dh)
    block_tables: jnp.ndarray,  # (B, max_pages) int32
    context_lens: jnp.ndarray,  # (B,) int32
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    B, H, Dh = q.shape
    P, page, KVH, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    group = H // KVH
    scale = scale if scale is not None else Dh**-0.5

    grid = (B, H, max_pages)

    def q_map(b, h, p, *_refs):
        return (b, h, 0)

    def kv_map(b, h, p, block_tables_ref, context_lens_ref):
        # THE hybrid-pointer dereference: page id out of the table in SMEM.
        return (block_tables_ref[b, p], 0, h // group, 0)

    def o_map(b, h, p, *_refs):
        return (b, h, 0)

    kernel = functools.partial(_paged_kernel, scale=scale, page=page)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, Dh), q_map),
                pl.BlockSpec((1, page, 1, Dh), kv_map),
                pl.BlockSpec((1, page, 1, Dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, Dh), o_map),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret_mode(interpret),
    )(block_tables, context_lens, q, k_pages, v_pages)
