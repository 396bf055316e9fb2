"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.binary_ip import binary_ip, estimate_dist2
from repro.kernels.binary_ip.ref import binary_ip_ref, estimate_dist2_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.int4_dist import int4_dist2
from repro.kernels.int4_dist.ref import int4_dist2_ref
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


RNG = np.random.default_rng(7)


# ------------------------------------------------------------------ binary_ip


@pytest.mark.parametrize("B,N,d", [(1, 1, 8), (4, 10, 64), (128, 256, 128),
                                   (33, 777, 256), (5, 64, 1024),
                                   (8, 300, 480), (16, 512, 960)])
def test_binary_ip_matches_ref(B, N, d):
    q = RNG.standard_normal((B, d)).astype(np.float32)
    codes = RNG.integers(0, 256, size=(N, d // 8)).astype(np.uint8)
    np.testing.assert_allclose(
        binary_ip(q, codes), binary_ip_ref(q, codes), rtol=1e-5, atol=1e-4
    )


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_binary_ip_dtypes(dtype):
    q = jnp.asarray(RNG.standard_normal((16, 128)), dtype=dtype)
    codes = RNG.integers(0, 256, size=(64, 16)).astype(np.uint8)
    out = binary_ip(q, codes)
    ref = binary_ip_ref(jnp.asarray(q, jnp.float32), codes)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-1)


@pytest.mark.parametrize("B,N,d", [(8, 300, 480), (16, 512, 960)])
def test_estimate_dist2_matches_ref_wide(B, N, d):
    """GIST-width level-1 estimates: the bit-plane kernel against the
    unpack-then-GEMM oracle."""
    q = RNG.standard_normal((B, d)).astype(np.float32)
    codes = RNG.integers(0, 256, size=(N, d // 8)).astype(np.uint8)
    norms = RNG.uniform(0.5, 2.0, N).astype(np.float32)
    ip_bar = RNG.uniform(0.6, 0.9, N).astype(np.float32)
    np.testing.assert_allclose(
        estimate_dist2(q, codes, norms, ip_bar),
        estimate_dist2_ref(q, codes, norms, ip_bar), rtol=1e-4, atol=1e-3,
    )


def test_estimate_matches_host_quantizer(small_ds, small_qb):
    """The device kernel must agree with the numpy host-plane estimator —
    the two planes share one index format."""
    from repro.core.quant import RabitQuantizer

    qb = small_qb
    q = small_ds.queries[:8]
    qr = (q - qb.centroid) @ qb.rotation.T
    dev = estimate_dist2(
        jnp.asarray(qr), jnp.asarray(qb.binary_codes),
        jnp.asarray(qb.norms), jnp.asarray(qb.ip_bar),
    )
    for i in range(8):
        pq = RabitQuantizer.prepare_query(qb, q[i])
        host = RabitQuantizer.estimate_dist2(qb, pq, np.arange(qb.norms.shape[0]))
        np.testing.assert_allclose(np.asarray(dev)[i], host, rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------------ int4_dist


@pytest.mark.parametrize("B,N,d", [(1, 1, 8), (3, 7, 64), (64, 200, 128),
                                   (8, 300, 480), (16, 512, 960)])
def test_int4_dist_matches_ref(B, N, d):
    d = d + (d % 2)
    q = RNG.standard_normal((B, d)).astype(np.float32)
    codes = RNG.integers(0, 256, (N, d // 2)).astype(np.uint8)
    lo = RNG.uniform(-2, -1, N).astype(np.float32)
    step = RNG.uniform(0.1, 0.3, N).astype(np.float32)
    np.testing.assert_allclose(
        int4_dist2(q, codes, lo, step), int4_dist2_ref(q, codes, lo, step),
        rtol=1e-4, atol=1e-3,
    )


def test_int4_matches_host_refine(small_ds, small_qb):
    from repro.core.quant import RabitQuantizer

    qb = small_qb
    q = small_ds.queries[:4]
    qr = (q - qb.centroid) @ qb.rotation.T
    ids = np.arange(256)
    dev = int4_dist2(
        jnp.asarray(qr), jnp.asarray(qb.ext_codes[ids]),
        jnp.asarray(qb.ext_lo[ids]), jnp.asarray(qb.ext_step[ids]),
    )
    for i in range(4):
        pq = RabitQuantizer.prepare_query(qb, q[i])
        host = RabitQuantizer.refine_dist2(qb, pq, ids)
        np.testing.assert_allclose(np.asarray(dev)[i], host, rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------ flash_attention


@pytest.mark.parametrize(
    "B,H,KVH,Sq,Skv,Dh,causal,window",
    [
        (1, 4, 2, 128, 128, 64, True, None),
        (2, 4, 1, 64, 192, 32, True, None),     # GQA + cross lengths + padding
        (1, 2, 2, 100, 100, 64, True, 37),      # sliding window, ragged tiles
        (1, 2, 2, 96, 96, 64, False, None),     # bidirectional (whisper encoder)
        (1, 8, 8, 256, 256, 128, True, None),
        (1, 4, 4, 128, 384, 64, True, 128),     # window + long KV (gemma3 local)
    ],
)
def test_flash_matches_ref(B, H, KVH, Sq, Skv, Dh, causal, window):
    q = RNG.standard_normal((B, H, Sq, Dh)).astype(np.float32)
    k = RNG.standard_normal((B, KVH, Skv, Dh)).astype(np.float32)
    v = RNG.standard_normal((B, KVH, Skv, Dh)).astype(np.float32)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), causal=True
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=5e-2, atol=5e-2
    )


# ------------------------------------------------------------ paged_attention


@pytest.mark.parametrize(
    "B,H,KVH,Dh,P,page,max_pages",
    [
        (2, 4, 2, 64, 16, 16, 4),
        (3, 8, 8, 32, 32, 8, 6),
        (1, 4, 1, 128, 8, 32, 3),
        (4, 2, 2, 64, 64, 16, 8),
    ],
)
def test_paged_matches_ref(B, H, KVH, Dh, P, page, max_pages):
    q = RNG.standard_normal((B, H, Dh)).astype(np.float32)
    kp = RNG.standard_normal((P, page, KVH, Dh)).astype(np.float32)
    vp = RNG.standard_normal((P, page, KVH, Dh)).astype(np.float32)
    bt = RNG.integers(0, P, (B, max_pages)).astype(np.int32)
    cl = RNG.integers(1, max_pages * page + 1, (B,)).astype(np.int32)
    out = paged_attention(q, kp, vp, bt, cl)
    ref = paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_paged_short_context():
    """context_len smaller than one page: only valid slots contribute."""
    B, H, KVH, Dh, P, page, max_pages = 1, 2, 2, 32, 4, 16, 2
    q = RNG.standard_normal((B, H, Dh)).astype(np.float32)
    kp = RNG.standard_normal((P, page, KVH, Dh)).astype(np.float32)
    vp = RNG.standard_normal((P, page, KVH, Dh)).astype(np.float32)
    bt = np.asarray([[2, 0]], np.int32)
    cl = np.asarray([3], np.int32)
    out = paged_attention(q, kp, vp, bt, cl)
    ref = paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
