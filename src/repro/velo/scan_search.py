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

import jax
import jax.numpy as jnp

from repro.kernels.binary_ip.ops import binary_ip
from repro.velo.index import DeviceIndex

DEFAULT_CHUNK = 32768


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
        qr = (queries - index.centroid[None, :]) @ index.rotation.T
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
            neg, cand = jax.lax.top_k(-est, C)
    else:
        nb = n // chunk
        tail = n - nb * chunk
        with jax.named_scope("velo.scan.stage1"):
            cb = codes[: nb * chunk].reshape(nb, chunk, -1)
            nrb = index.norms[: nb * chunk].reshape(nb, chunk)
            ipb = index.ip_bar[: nb * chunk].reshape(nb, chunk)

        def body(carry, blk):
            best_d, best_i = carry
            codes_blk, norms_blk, ipb_blk, bi = blk
            est = stage1_block(codes_blk, norms_blk, ipb_blk)     # (B, chunk)
            # top-C of the CHUNK first, then a tiny 2C merge with the carry —
            # sorting concat(C + chunk) repays the C columns every chunk and
            # copies the concat (§Perf iteration 4).  NOTE: the residual sort
            # volume is a CPU-lowering artifact: XLA CPU lowers top_k to a
            # full variadic sort; the TPU backend emits a partial-reduction
            # TopK custom call, and the production path fuses selection into
            # the Pallas stage-1 kernel entirely (running top-C in VMEM).
            with jax.named_scope("velo.scan.select"):
                negc, selc = jax.lax.top_k(-est, C)
                ids = bi * chunk + selc.astype(jnp.int32)
                all_d = jnp.concatenate([best_d, -negc], axis=1)  # (B, 2C)
                all_i = jnp.concatenate([best_i, ids], axis=1)
                negd, sel = jax.lax.top_k(-all_d, C)
                return (-negd, jnp.take_along_axis(all_i, sel, axis=1)), None

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
                ids = nb * chunk + jnp.arange(tail, dtype=jnp.int32)[None, :]
                all_d = jnp.concatenate([best_d, est], axis=1)
                all_i = jnp.concatenate(
                    [best_i, jnp.broadcast_to(ids, est.shape)], axis=1)
                negd, sel = jax.lax.top_k(-all_d, C)
                best_d, best_i = -negd, jnp.take_along_axis(all_i, sel, axis=1)
        cand = best_i

    # ---- stage 2: gather top-C, int4 refine
    with jax.named_scope("velo.scan.rerank"):
        packed = index.ext_codes[cand].astype(jnp.int32)    # (B, C, d/2)
        lo4 = (packed & 0xF).astype(jnp.float32)
        hi4 = ((packed >> 4) & 0xF).astype(jnp.float32)
        codes4 = jnp.stack([lo4, hi4], axis=-1).reshape(B, C, d)
        x = codes4 * index.ext_step[cand][..., None] + index.ext_lo[cand][..., None]
        diff = qr[:, None, :] - x
        refined = jnp.einsum("bcd,bcd->bc", diff, diff)     # (B, C)

        kk = min(k, C)
        negk, sel = jax.lax.top_k(-refined, kk)
        ids = jnp.take_along_axis(cand, sel, axis=1).astype(jnp.int32)
        return ids, -negk
