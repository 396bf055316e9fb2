"""The scan's select stage: ``scan_search.smallest`` against ``lax.top_k``,
and ``scan_search`` against a copy of itself that selects with ``lax.top_k``.

Both must agree bit for bit: values, columns, ids and distances."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.quant import RabitQuantizer
from repro.velo import scan_search as ss
from repro.velo.index import DeviceIndex, from_host, refine_rows


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(jnp.asarray(x), jnp.uint16))


def _row(kind: str, rng, b: int, n: int) -> jnp.ndarray:
    if kind == "random":
        x = rng.standard_normal((b, n)) * 100.0
    elif kind == "ties":      # a handful of distinct values
        x = np.round(rng.standard_normal((b, n)) * 2.0)
    elif kind == "signed_zero":
        x = rng.choice([0.0, -0.0, 0.5, -0.5], size=(b, n))
    else:
        raise ValueError(kind)
    return jnp.asarray(x, jnp.bfloat16)


SELECT_CASES = [
    # (kind, B, n, C, carry width m, g or None for the top_k fallback)
    ("random", 4, 4096, 64, 0, 8),
    ("ties", 4, 4096, 64, 0, 8),
    ("signed_zero", 4, 4096, 64, 0, 8),
    ("random", 2, 32768, 512, 0, 8),          # a whole chunk
    ("ties", 2, 32768, 512, 512, 8),          # a chunk after the carry
    ("signed_zero", 2, 32768, 512, 512, 8),
    ("random", 2, 20624, 512, 512, 8),        # the GIST cell's tail merge
    ("ties", 2, 20624, 512, 512, 8),
    ("ties", 2, 21136, 512, 0, 8),            # the tail's width, no carry
    ("random", 3, 1024, 64, 0, 8),            # n/g exactly 2C
    ("ties", 3, 512, 64, 64, 8),              # n/g exactly C
    ("random", 3, 4100, 64, 64, 8),           # padded to whole groups
    ("random", 3, 32768, 64, 64, 16),         # wider rows, larger groups
    ("random", 3, 1023, 128, 0, None),        # n/8 < C: top_k
    ("ties", 3, 1023, 128, 128, None),
    ("signed_zero", 2, 70000, 64, 0, None),   # a column needs 17 bits: top_k
    ("ties", 2, 65500, 64, 64, None),         # so does a column after the carry
]


@pytest.mark.parametrize("kind,b,n,c,m,g", SELECT_CASES)
def test_smallest_matches_top_k(kind, b, n, c, m, g):
    assert ss._group_size(n, c, m) == g
    rng = np.random.default_rng(n + c + m)
    x = _row(kind, rng, b, n)
    if not m:
        got_v, got_i = jax.jit(ss.smallest, static_argnums=1)(x, c)
        neg, want_i = jax.lax.top_k(-x, c)
    else:
        base = 7 * n
        carry = (_row(kind, rng, b, m),
                 jnp.asarray(rng.permutation(b * m).reshape(b, m), jnp.int32))
        got_v, got_i = jax.jit(ss.smallest, static_argnums=1)(x, c, carry, base)
        neg, col = jax.lax.top_k(-jnp.concatenate([carry[0], x], axis=1), c)
        col = np.asarray(col)
        want_i = np.where(col < m, np.take_along_axis(
            np.asarray(carry[1]), np.minimum(col, m - 1), axis=1), base + col - m)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(_bits(got_v), _bits(-neg))


# ------------------------------------------------------------ whole scan


@functools.partial(jax.jit, static_argnames=("refine", "k", "rerank", "use_kernel", "chunk"))
def scan_search_top_k(index: DeviceIndex, refine, queries, k, rerank, use_kernel, chunk):
    """``scan_search`` as it was before ``smallest``: each select is a
    ``lax.top_k`` (per chunk, then a 2C merge with the carry, then the tail);
    ``refine`` is the rerank over the three level-2 tables
    (``int4_three_tables``), as it was before ``ext_rows``."""
    from repro.kernels.binary_ip.ops import binary_ip
    from repro.kernels.binary_ip.ref import binary_ip_ref

    B, d = queries.shape
    qr = (queries - index.centroid[None, :]) @ index.rotation.T
    qnorm = jnp.linalg.norm(qr, axis=1, keepdims=True)
    qunit = qr / jnp.maximum(qnorm, 1e-12)
    codes = index.binary_codes[:-1]
    n = codes.shape[0]
    C = min(rerank, n)

    def stage1_block(codes_blk, norms_blk, ipb_blk):
        if use_kernel:
            g = binary_ip(qunit.astype(jnp.bfloat16), codes_blk)
        else:
            g = binary_ip_ref(qunit.astype(jnp.bfloat16), codes_blk)
        g = (g / jnp.sqrt(jnp.float32(d))).astype(jnp.bfloat16)
        ipb = jnp.maximum(ipb_blk[None, :], 1e-6).astype(jnp.bfloat16)
        est_cos = jnp.clip(g / ipb, -1.0, 1.0)
        nr = norms_blk[None, :].astype(jnp.bfloat16)
        qn = qnorm.astype(jnp.bfloat16)
        return qn**2 + nr**2 - 2.0 * qn * nr * est_cos

    if n <= chunk:
        est = stage1_block(codes, index.norms[:-1], index.ip_bar[:-1])
        _, cand = jax.lax.top_k(-est, C)
    else:
        nb = n // chunk
        tail = n - nb * chunk
        cb = codes[: nb * chunk].reshape(nb, chunk, -1)
        nrb = index.norms[: nb * chunk].reshape(nb, chunk)
        ipb = index.ip_bar[: nb * chunk].reshape(nb, chunk)

        def body(carry, blk):
            best_d, best_i = carry
            codes_blk, norms_blk, ipb_blk, bi = blk
            est = stage1_block(codes_blk, norms_blk, ipb_blk)
            negc, selc = jax.lax.top_k(-est, C)
            ids = bi * chunk + selc.astype(jnp.int32)
            all_d = jnp.concatenate([best_d, -negc], axis=1)
            all_i = jnp.concatenate([best_i, ids], axis=1)
            negd, sel = jax.lax.top_k(-all_d, C)
            return (-negd, jnp.take_along_axis(all_i, sel, axis=1)), None

        init = (jnp.full((B, C), jnp.bfloat16(3e38)), jnp.zeros((B, C), jnp.int32))
        (best_d, best_i), _ = jax.lax.scan(
            body, init, (cb, nrb, ipb, jnp.arange(nb, dtype=jnp.int32)))
        if tail:
            est = stage1_block(codes[nb * chunk:], index.norms[nb * chunk: n],
                               index.ip_bar[nb * chunk: n])
            ids = nb * chunk + jnp.arange(tail, dtype=jnp.int32)[None, :]
            all_d = jnp.concatenate([best_d, est], axis=1)
            all_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, est.shape)], axis=1)
            negd, sel = jax.lax.top_k(-all_d, C)
            best_i = jnp.take_along_axis(all_i, sel, axis=1)
        cand = best_i

    refined = refine(cand, qr)
    negk, sel = jax.lax.top_k(-refined, min(k, C))
    return jnp.take_along_axis(cand, sel, axis=1).astype(jnp.int32), -negk


@functools.lru_cache(maxsize=None)
def _scan_data(n: int, copies: int, d: int = 64, nq: int = 24):
    """A device index over n rows (each distinct vector repeated ``copies``
    times, so equal estimates fall in different chunks) and its queries."""
    rng = np.random.default_rng(n * 10 + copies)
    base = rng.standard_normal((-(-n // copies), d)).astype(np.float32)
    base = np.tile(base, (copies, 1))[:n]
    qb = RabitQuantizer(d, seed=0).fit_encode(base)
    queries = base[rng.choice(n, nq, replace=False)] + 0.3 * rng.standard_normal(
        (nq, d)).astype(np.float32)
    return qb, from_host(qb), jnp.asarray(queries)


SCAN_CASES = [
    # (n, chunk, rerank, copies, use_kernel)
    (5000, 1024, 64, 1, False),      # 4 chunks and a tail, grouped select
    (5000, 1024, 64, 4, False),      # duplicated rows: ties across chunks
    (4096, 1024, 64, 1, False),      # no tail
    (800, 1024, 64, 1, False),       # one block, no carry
    (5000, 1024, 200, 2, False),     # 1024 + 200 < 8 * 200: top_k fallback
    (3000, 1024, 64, 3, True),       # the binary_ip kernel (interpreted)
]


@pytest.mark.parametrize("n,chunk,rerank,copies,use_kernel", SCAN_CASES)
def test_scan_search_matches_top_k_select(n, chunk, rerank, copies, use_kernel,
                                          int4_three_tables):
    qb, index, queries = _scan_data(n, copies)
    kw = dict(k=10, rerank=rerank, use_kernel=use_kernel, chunk=chunk)
    ids, d2 = ss.scan_search(index, queries, **kw)
    want_ids, want_d2 = scan_search_top_k(index, int4_three_tables(qb), queries, **kw)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(
        np.asarray(d2).view(np.uint32), np.asarray(want_d2).view(np.uint32))


@pytest.mark.parametrize("d", [64, 96, 960])
def test_refine_rows_matches_three_tables(d, int4_three_tables):
    """One gather of ``ext_rows`` refines to the three-table formula's
    distances bit for bit, the sentinel row among the ids."""
    rng = np.random.default_rng(d)
    n, b, m = 300, 5, 40
    qb = RabitQuantizer(d, seed=1).fit_encode(rng.standard_normal((n, d)).astype(np.float32))
    ids = rng.integers(0, n + 1, (b, m)).astype(np.int32)
    ids[:, 0] = n
    qr = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
    got = jax.jit(refine_rows)(from_host(qb), jnp.asarray(ids), qr)
    want = jax.jit(int4_three_tables(qb))(jnp.asarray(ids), qr)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
