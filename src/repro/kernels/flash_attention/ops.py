"""Public wrapper: pads sequence lengths to tile multiples and dispatches.

The models call this for prefill; interpret mode on CPU (oracle-validated),
compiled pallas on TPU.  Padding policy:
  causal:     pad queries at the FRONT, keys at the BACK; real query i keeps
              position i + (Skv - Sq) via an explicit offset, padded keys are
              masked by kv_valid.
  non-causal: pad queries and keys at the BACK; padded key columns masked by
              kv_valid; padded query rows sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as _k


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "interpret")
)
def flash_attention(
    q: jnp.ndarray,   # (B, H, Sq, Dh)
    k: jnp.ndarray,   # (B, KVH, Skv, Dh)
    v: jnp.ndarray,   # (B, KVH, Skv, Dh)
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    B, H, Sq, Dh = q.shape
    Skv = k.shape[2]
    bq = min(_k.DEFAULT_BQ, max(8, Sq))
    bk = min(_k.DEFAULT_BK, max(8, Skv))
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk

    if not (pad_q or pad_k):
        return _k.flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            bq=bq, bk=bk, interpret=interpret,
        )

    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if causal:
        qp = jnp.pad(q, ((0, 0), (0, 0), (pad_q, 0), (0, 0)))
        out = _k.flash_attention_pallas(
            qp, kp, vp, causal=True, window=window, scale=scale,
            offset=Skv - Sq - pad_q, kv_valid=Skv,
            bq=bq, bk=bk, interpret=interpret,
        )
        return out[:, :, pad_q:, :]
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    out = _k.flash_attention_pallas(
        qp, kp, vp, causal=False, window=window, scale=scale,
        offset=Skv - Sq, kv_valid=Skv,
        bq=bq, bk=bk, interpret=interpret,
    )
    return out[:, :, :Sq, :]
