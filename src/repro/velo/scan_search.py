"""Two-stage compressed scan: binary MXU sweep -> int4 rerank (beyond-paper mode).

On a CPU+SSD, graph traversal wins because it touches ~L of n records.  On a
TPU shard the economics flip: the level-1 codes of a few million vectors fit
in HBM (d/8 bytes each), and the MXU turns the full binary scan into a dense
GEMM running at roofline — no data-dependent gathers, no traversal serialism.
VeloANN's own compression makes this possible: this mode is the paper's
level-1/level-2 hierarchy with the traversal replaced by a scan, and is what
the veloann serve cell lowers for the multi-pod dry-run (each of 512 chips
scans its corpus shard; results merge by distributed top-k).

Stage 1 STREAMS over corpus chunks (lax.scan) keeping a running top-C per
query — materializing the full (B, n) estimate matrix would need
query_batch x shard_size x 4 B = 32 GiB/device at production sizes (measured;
chunking brings the working set to B x chunk ~ 0.5 GiB).
Stage 2 gathers the surviving top-C candidates and refines them with the
int4 codes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.binary_ip.ops import binary_ip
from repro.velo.index import DeviceIndex, refine_rows

DEFAULT_CHUNK = 32768

_POS_BITS = 16                # a packed key's low bits: the column in the row
_POS_MASK = (1 << _POS_BITS) - 1
_PAD_KEY = jnp.iinfo(jnp.int32).max


def _group_size(n: int, c: int, m: int = 0) -> int | None:
    """Group size of ``smallest``'s two-level selection of ``c`` from rows
    of ``n`` columns after a carry of ``m``, or None where it does not apply
    and ``lax.top_k`` selects.

    The two sorts it leaves, of ceil(n/g) group minima and of c*g
    candidates, are smallest together near g = sqrt(n/c); g is that rounded
    to a power of two, at least 8.  None where a column does not fit the
    key's low bits or where n/8 < c (too few groups)."""
    if m + n >= 1 << _POS_BITS or n < 8 * c:
        return None
    return max(8, 1 << round(math.log2(n / c) / 2))


def _pack(x: jnp.ndarray, col: jnp.ndarray) -> jnp.ndarray:
    """bf16 values and their columns -> int32 keys that order as (value in
    IEEE total order, column): the value's bits, made monotone, above the
    column.  Unique within a row."""
    s = jax.lax.bitcast_convert_type(x, jnp.int16).astype(jnp.int32)
    s = s ^ ((s >> 15) & 0x7FFF)      # negatives: flip the magnitude bits
    return (s << _POS_BITS) | col


def _unpack(key: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of ``_pack``: (bf16 values, int32 columns), bit for bit."""
    s = key >> _POS_BITS
    s = s ^ ((s >> 15) & 0x7FFF)
    x = jax.lax.bitcast_convert_type(s.astype(jnp.int16), jnp.bfloat16)
    return x, key & _POS_MASK


def smallest(
    x: jnp.ndarray, c: int, carry: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    base=0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The ``c`` smallest entries of each row of ``x`` (B, n) bf16 and their
    columns, exactly as ``v, i = lax.top_k(-x, c)`` gives ``-v, i``: ascending
    in IEEE total order (-0 before +0), ties to the lower column.

    With ``carry`` = (values (B, m) bf16, ids (B, m) int32), the entries of
    an earlier selection placed before ``x``'s columns, it returns the c
    smallest of both with their ids: the carry's own, or ``base`` + column.

    ``lax.top_k`` over a wide row is a full two-operand (value, iota) sort of
    the row on the TPU, not a partial selection.  Here each entry becomes one
    unique int32 key (``_pack``), and the selection is exact in two levels:
    the row's groups of g keys (g from ``_group_size``) give their minima;
    the c groups with the smallest minima hold every one of the c smallest
    keys (each such key's group has a minimum no larger than the c-th
    smallest key, and at most c groups do); sorting those c*g keys, and the
    carry's, gives the answer.  One-operand sorts of ~n/g and c*g keys
    replace a two-operand sort of n; the carry's ids ride along its sort as
    a second operand, since a gather of them costs more than the sort."""
    if x.dtype != jnp.bfloat16:
        raise TypeError(f"smallest packs bf16 values, not {x.dtype}")
    b, n = x.shape
    m = 0 if carry is None else carry[0].shape[1]
    g = _group_size(n, c, m)
    if g is None:
        if carry is not None:
            x = jnp.concatenate([carry[0], x], axis=1)
        neg, col = jax.lax.top_k(-x, c)
        if carry is None:
            return -neg, col
        kept = jnp.take_along_axis(carry[1], jnp.minimum(col, m - 1), axis=1)
        return -neg, jnp.where(col < m, kept, base + col - m)
    groups = -(-n // g)
    col = jax.lax.broadcasted_iota(jnp.int32, (b, groups * g), 1) + m
    xp = jnp.pad(x, ((0, 0), (0, groups * g - n)))
    keys = jnp.where(col < m + n, _pack(xp, col), _PAD_KEY).reshape(b, groups, g)
    grp = ((_lowest(keys.min(axis=2), c) & _POS_MASK) - m) // g
    cand = jnp.take_along_axis(keys, grp[:, :, None], axis=1).reshape(b, c * g)
    if carry is None:
        return _unpack(_lowest(cand, c))
    own = _pack(carry[0], jax.lax.broadcasted_iota(jnp.int32, (b, m), 1))
    keys, ids = jax.lax.sort(
        (jnp.concatenate([own, cand], axis=1),
         jnp.concatenate([carry[1], base + (cand & _POS_MASK) - m], axis=1)),
        dimension=1, is_stable=False, num_keys=1)
    return _unpack(keys[:, :c])[0], ids[:, :c]


def _lowest(keys: jnp.ndarray, c: int) -> jnp.ndarray:
    """The ``c`` smallest keys of each row, ascending.  The keys are unique,
    so the sort need not be stable, and an unstable sort is one operand on
    the TPU (a stable one gets an iota operand to break ties)."""
    return jax.lax.sort(keys, dimension=1, is_stable=False)[:, :c]


@functools.partial(
    jax.jit, static_argnames=("k", "rerank", "interpret", "use_kernel", "chunk")
)
def scan_search(
    index: DeviceIndex,
    queries: jnp.ndarray,     # (B, d)
    k: int = 10,
    rerank: int = 64,         # candidates refined in stage 2 (C)
    interpret: bool | None = None,
    use_kernel: bool = True,  # False: pure-jnp GEMM (dry-run lowering path —
                              # interpret-mode Pallas would unroll the grid
                              # into the HLO; on real TPUs use_kernel=True)
    chunk: int = DEFAULT_CHUNK,
):
    """Returns (ids (B, k) int32, dist2 (B, k) f32).

    The stages carry ``jax.named_scope`` names, so a profile attributes each
    device operation to one of them: ``velo.scan.stage1`` (query rotation,
    ``binary_ip`` and the bf16 estimate), ``velo.scan.select`` (the per-chunk
    top-C, the carry merge and the tail merge) and ``velo.scan.rerank`` (the
    int4 gather and refine, and the final top-k)."""
    B, d = queries.shape
    with jax.named_scope("velo.scan.stage1"):
        # float32, as the rerank's reference (refine_batch) rotates: at the
        # TPU's default precision the operands are rounded to bf16, which
        # moved the rerank's distances by up to ~6e-4 relative.  B x d x d
        # takes microseconds even at HIGHEST's six passes.
        qr = jnp.matmul(queries - index.centroid[None, :], index.rotation.T,
                        precision=jax.lax.Precision.HIGHEST)
        qnorm = jnp.linalg.norm(qr, axis=1, keepdims=True)
        qunit = qr / jnp.maximum(qnorm, 1e-12)
        codes = index.binary_codes[:-1]  # drop sentinel row
    n = codes.shape[0]
    C = min(rerank, n)

    def stage1_block(codes_blk, norms_blk, ipb_blk):
        """Level-1 estimates for one corpus block: -> (B, blk) bf16.

        bf16 end-to-end (§Perf iteration 4): the level-1 estimate is a
        STEERING value re-ranked by int4 refinement, so bf16's ~3 decimal
        digits lose nothing (recall checked in tests), while the dominant
        HBM streams — unpacked sign lanes and the (B, chunk) estimate
        tensor — halve."""
        with jax.named_scope("velo.scan.stage1"):
            if use_kernel:
                g = binary_ip(qunit.astype(jnp.bfloat16), codes_blk,
                              interpret=interpret)
            else:
                from repro.kernels.binary_ip.ref import binary_ip_ref

                g = binary_ip_ref(qunit.astype(jnp.bfloat16), codes_blk)
            g = (g / jnp.sqrt(jnp.float32(d))).astype(jnp.bfloat16)
            ipb = jnp.maximum(ipb_blk[None, :], 1e-6).astype(jnp.bfloat16)
            est_cos = jnp.clip(g / ipb, -1.0, 1.0)
            nr = norms_blk[None, :].astype(jnp.bfloat16)
            qn = qnorm.astype(jnp.bfloat16)
            return qn**2 + nr**2 - 2.0 * qn * nr * est_cos

    if n <= chunk:
        est = stage1_block(codes, index.norms[:-1], index.ip_bar[:-1])
        with jax.named_scope("velo.scan.select"):
            _, cand = smallest(est, C)
    else:
        nb = n // chunk
        tail = n - nb * chunk
        with jax.named_scope("velo.scan.stage1"):
            cb = codes[: nb * chunk].reshape(nb, chunk, -1)
            nrb = index.norms[: nb * chunk].reshape(nb, chunk)
            ipb = index.ip_bar[: nb * chunk].reshape(nb, chunk)

        def body(carry, blk):
            codes_blk, norms_blk, ipb_blk, bi = blk
            est = stage1_block(codes_blk, norms_blk, ipb_blk)     # (B, chunk)
            with jax.named_scope("velo.scan.select"):
                return smallest(est, C, carry, bi * chunk), None

        init = (
            jnp.full((B, C), jnp.bfloat16(3e38)),
            jnp.zeros((B, C), jnp.int32),
        )
        (best_d, best_i), _ = jax.lax.scan(
            body, init,
            (cb, nrb, ipb, jnp.arange(nb, dtype=jnp.int32)),
        )
        if tail:
            est = stage1_block(
                codes[nb * chunk:], index.norms[nb * chunk : n], index.ip_bar[nb * chunk : n]
            )
            with jax.named_scope("velo.scan.select"):
                best_d, best_i = smallest(est, C, (best_d, best_i), nb * chunk)
        cand = best_i

    # ---- stage 2: one gather of the top-C's level-2 rows, int4 refine
    with jax.named_scope("velo.scan.rerank"):
        refined = refine_rows(index, cand, qr)              # (B, C)

        kk = min(k, C)
        negk, sel = jax.lax.top_k(-refined, kk)
        ids = jnp.take_along_axis(cand, sel, axis=1).astype(jnp.int32)
        return ids, -negk
