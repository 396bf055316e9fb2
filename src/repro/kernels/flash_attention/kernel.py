"""Pallas TPU kernel: FlashAttention-style fused attention (prefill path).

Online-softmax attention with explicit VMEM tiling:

  grid = (B, H, Sq/BQ, Skv/BK)   — the last (kv) axis is the TPU's sequential
  innermost grid dimension, so running max/denominator/accumulator live in
  VMEM scratch across kv steps and are finalized on the last step
  (FlashAttention's streaming recurrence mapped onto the Pallas grid).

Supports GQA (kv-head index derived in the BlockSpec index_map — no repeated
KV in HBM), causal masking, and sliding windows (gemma3's 5:1 local:global
pattern and jamba's long-context attention layers use the window path).

VMEM per cell: BQ*Dh + 2*BK*Dh + BQ*BK logits + BQ*Dh accumulator
~= (128*128 + 2*128*128 + 128*128 + 128*128) * 4B ~= 0.4 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *, scale: float, causal: bool, window: int | None, bq: int, bk: int,
    offset: int, kv_valid: int,
):
    """offset: key position of padded-query row 0 (so q_pos = row + offset);
    kv_valid: number of real (unpadded) keys."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q = q_ref[0, 0].astype(jnp.float32)        # (BQ, Dh)
    k = k_ref[0, 0].astype(jnp.float32)        # (BK, Dh)
    v = v_ref[0, 0].astype(jnp.float32)        # (BK, Dh)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # (BQ, BK)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < kv_valid                     # padded keys never attended
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scratch[...]                     # (BQ, 1)
    l_prev = l_scratch[...]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)                 # (BQ, BK)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

    acc = acc_scratch[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scratch[...] = m_new
    l_scratch[...] = l_new
    acc_scratch[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scratch[...] / jnp.maximum(l_scratch[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "scale", "bq", "bk", "offset", "kv_valid", "interpret"
    ),
)
def flash_attention_pallas(
    q: jnp.ndarray,   # (B, H, Sq, Dh)
    k: jnp.ndarray,   # (B, KVH, Skv, Dh)
    v: jnp.ndarray,   # (B, KVH, Skv, Dh)
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    offset: int | None = None,     # default: right-align queries to keys
    kv_valid: int | None = None,   # default: all keys valid
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    B, H, Sq, Dh = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    assert H % KVH == 0 and Sq % bq == 0 and Skv % bk == 0
    group = H // KVH
    scale = scale if scale is not None else Dh**-0.5
    offset = offset if offset is not None else (Skv - Sq)
    kv_valid = kv_valid if kv_valid is not None else Skv

    grid = (B, H, Sq // bq, Skv // bk)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, offset=offset, kv_valid=kv_valid,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, Dh), jnp.float32),   # output accumulator
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
