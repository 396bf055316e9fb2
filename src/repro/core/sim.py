"""Discrete-event hardware model: NVMe SSD + CPU cost accounting.

This container has no SSD and one CPU core, so wall-clock cannot be measured.
Instead the *real* algorithms (real index, real buffer pool, real searches)
run to completion and are charged simulated time from this model.  Recall,
I/O counts, and hit rates are therefore exact; only seconds are modeled.

Constants are calibrated to the paper's testbed class (Solidigm NVMe,
Xeon 8457C):
  * 4 KB random read ~80 us end-to-end at low queue depth, ~3 GB/s streaming,
    queue depth 32 per device as io_uring would drive it;
  * one fp32 distance ~1 ns/dim on one core (AVX-512 FMA at realistic IPC);
  * binary (popcount) distance ~0.05 ns/dim; 4-bit dequant distance ~0.5 ns/dim;
  * stackless coroutine switch 50 ns ("less than a last-level cache miss",
    paper §2.3).
"""

from __future__ import annotations

import dataclasses
import heapq
import math


@dataclasses.dataclass
class SSDConfig:
    read_latency_s: float = 80e-6     # fixed cost per random read
    bandwidth_bps: float = 3.0e9      # per-device streaming bandwidth
    queue_depth: int = 32             # concurrent in-flight commands


class SSD:
    """Queue-depth-limited device: a read occupies one of QD channels."""

    def __init__(self, config: SSDConfig | None = None):
        self.config = config or SSDConfig()
        self._channels: list[float] = [0.0] * self.config.queue_depth
        heapq.heapify(self._channels)
        self.reads = 0
        self.bytes_read = 0

    def submit(self, t_now: float, nbytes: int) -> float:
        """Issue one read at time t_now; returns absolute completion time."""
        free_at = heapq.heappop(self._channels)
        start = max(t_now, free_at)
        done = start + self.config.read_latency_s + nbytes / self.config.bandwidth_bps
        heapq.heappush(self._channels, done)
        self.reads += 1
        self.bytes_read += nbytes
        return done

    def reset(self) -> None:
        self._channels = [0.0] * self.config.queue_depth
        heapq.heapify(self._channels)
        self.reads = 0
        self.bytes_read = 0


@dataclasses.dataclass
class CostModel:
    dist_full_per_dim: float = 1.0e-9
    dist_binary_per_dim: float = 0.05e-9
    dist_ext_per_dim: float = 0.5e-9
    visit_overhead_s: float = 2.0e-6     # beam maintenance per explored vertex
    page_parse_s: float = 0.5e-6         # slot binary search / record locate
    record_decode_s: float = 0.4e-6      # adjacency decompress + payload split
    io_submit_s: float = 0.5e-6          # io_uring SQE prep + syscall amortized
    coroutine_switch_s: float = 50e-9
    batch_dispatch_s: float = 0.3e-6     # one kernel/ufunc dispatch per batched
                                         # distance evaluation, amortized over
                                         # all rows of the batch
    table_upload_s: float = 25e-6        # one-time pin of an index's resident
                                         # code tables on the distance engine
                                         # (host->device DMA of ~hundreds of KB
                                         # at PCIe rates), charged per
                                         # registered index, NOT per hop
    full_dispatch_s: float = 0.3e-6      # dispatch of an fp32 refine_full batch
                                         # (BLAS GEMV path), a constant apart
                                         # from the int4 refine dispatch; the
                                         # default equals batch_dispatch_s
    hbm_scatter_s: float = 1e-6          # one double-buffered scatter DMA that
                                         # installs a staged admit group into
                                         # HBM cache slots; overlapped with the
                                         # concurrent fused dispatch, so only
                                         # the non-hidden remainder is charged
    dist_hbm_per_dim: float = 0.05e-9    # 4-bit refinement of a record already
                                         # resident in an HBM cache slot: the
                                         # gather feeds the kernel from device
                                         # memory (no host decode / upload), so
                                         # the per-dim cost drops to near the
                                         # binary-scan rate
    shard_merge_s: float = 2e-6          # one small collective merging the
                                         # per-shard candidate slices of a
                                         # scattered score op into the global
                                         # result (the all_gather + top_k
                                         # idiom of repro.velo.dist_search);
                                         # charged once per multi-shard
                                         # scatter, never when one shard owns
                                         # every row (S=1 parity)
    beam_step_s: float = 0.4e-6          # one fused on-device beam step
                                         # (score + visited mask + top-k merge
                                         # + frontier select in a single
                                         # launch), amortized over every beam
                                         # op in the rendezvous flush group —
                                         # replaces the per-row distance
                                         # download the host path pays
    beam_visit_s: float = 0.5e-6         # residual host bookkeeping per
                                         # explored vertex when the beam lives
                                         # on device (frontier cursor + I/O
                                         # issue only); the insort/merge share
                                         # of visit_overhead_s moved into the
                                         # fused call

    def estimate(self, count: int, dim: int) -> float:
        """Level-1 binary distance estimates for `count` vertices."""
        return count * dim * self.dist_binary_per_dim

    def refine_ext(self, dim: int) -> float:
        """Level-2 4-bit refinement of one record."""
        return dim * self.dist_ext_per_dim

    def refine_full(self, dim: int) -> float:
        """Exact fp32 distance of one record (DiskANN-style refinement)."""
        return dim * self.dist_full_per_dim

    def hbm_refine_ext(self, dim: int) -> float:
        """Level-2 refinement of one record served from an HBM cache slot."""
        return dim * self.dist_hbm_per_dim

    def fused_batch_s(self, total_flop_s: float, kind: str = "quant") -> float:
        """One fused cross-query evaluation: the per-row flops of every
        participating query's rows plus a SINGLE kernel dispatch, amortized
        across the whole rendezvous batch (instead of one dispatch per query).
        ``kind`` selects the dispatch constant: fp32 ``refine_full`` batches
        ("full") launch through a different kernel than the quantized paths,
        and fused beam steps ("beam"/"beam_part") launch the combined
        score+merge+select call (``beam_step_s``)."""
        if kind.startswith("beam"):
            dispatch = self.beam_step_s
        elif kind == "full":
            dispatch = self.full_dispatch_s
        else:
            dispatch = self.batch_dispatch_s
        return dispatch + total_flop_s


@dataclasses.dataclass
class WorkloadStats:
    """Aggregated over a run of the engine."""

    n_queries: int = 0
    makespan_s: float = 0.0
    sum_latency_s: float = 0.0
    latencies: list[float] = dataclasses.field(default_factory=list)
    # query id of each ``latencies`` entry (completion order) — lets a
    # multi-tenant caller split the latency distribution by tenant
    latency_qids: list[int] = dataclasses.field(default_factory=list)
    # latency vs service time: with an SlaPlan attached, ``latencies`` are
    # completion - ARRIVAL (queue wait + service) while ``service_times``
    # keep the old completion - dispatch number; without a plan the two are
    # identical and queue_wait_s stays 0 (bitwise back-compat)
    sum_service_s: float = 0.0
    service_times: list[float] = dataclasses.field(default_factory=list)
    queue_wait_s: float = 0.0        # total seconds queries sat admitted-but-
                                     # undispatched (latency - service)
    # deadline accounting (SlaPlan with deadlines; zeros otherwise)
    deadline_hits: int = 0           # completions at/before their deadline
    deadline_misses: int = 0
    # charged coroutine switches (dispatches that paid coroutine_switch_s) —
    # the observable the rr/sla switch-accounting parity tests pin: a
    # preempted-then-resumed coroutine is charged exactly one switch under
    # either scheduler, and a flush's switch-free credit is spent exactly once
    coroutine_switches: int = 0
    io_count: int = 0
    coalesced_reads: int = 0   # reads served by an already in-flight page (no SQE)
    cache_hits: int = 0
    cache_misses: int = 0
    # record buffer pool pressure (shared pool, LOCKED-window coalescing)
    lock_waits: int = 0              # coroutines parked on a LOCKED slot
    coalesced_record_loads: int = 0  # parked waiters served by another's load
    group_admits: int = 0            # co-resident groups admitted in one clock
    clock_skips: int = 0             # clock steps that landed on LOCKED slots
    # per-tenant admission quotas (multi-tenant shared pool)
    quota_reclaims: int = 0          # slots an over-quota tenant took from itself
    quota_denials: int = 0           # slot acquisitions denied at the tenant
                                     # cap (nothing of the tenant's own was
                                     # evictable; an uncached demand admission
                                     # can contribute more than one)
    # cross-query fused score dispatch (engine rendezvous buffer)
    score_flushes: int = 0     # fused kernel dispatches issued by the engine
    score_requests: int = 0    # per-coroutine score ops absorbed by those flushes
    score_rows: int = 0        # total distance rows across all flushes
    cross_tenant_flushes: int = 0  # rendezvous flushes whose requests spanned
                                   # more than one tenant (serving plane)
    overlap_flushes: int = 0   # shared-rendezvous flushes issued while another
                               # worker's completions were still in flight
    # sharded scatter-gather serving plane (core.sharding)
    scatter_ops: int = 0       # scatter ops routed to owning shards
    shard_flushes: int = 0     # per-shard rendezvous flushes
    shard_merges: int = 0      # cross-shard top-k merges (multi-shard
                               # scatters only; single-shard scatters pass
                               # the owning shard's results through)
    # fused on-device beam steps (frontier replies instead of raw distances)
    beam_ops: int = 0          # per-coroutine beam ops absorbed by flushes
    beam_flushes: int = 0      # fused beam-step launches (one per beam group
                               # per flush — the ONE exchange per hop)
    beam_rows: int = 0         # fresh vertices scored inside beam steps
    dist_downloads: int = 0    # score/scatter replies that shipped raw
                               # per-row distances back to the host (beam
                               # replies return frontiers and do not count)
    # HBM record-cache tier (device-resident hot records above the host pool)
    hbm_hits: int = 0          # record lookups served from HBM cache slots
    hbm_misses: int = 0        # lookups that fell through to the host pool
    hbm_scatters: int = 0      # double-buffered scatter DMAs installing
                               # staged admit groups into slots
    hbm_evictions: int = 0     # slots reclaimed by the device clock sweep

    @property
    def qps(self) -> float:
        return self.n_queries / self.makespan_s if self.makespan_s > 0 else 0.0

    @property
    def mean_latency_ms(self) -> float:
        return 1e3 * self.sum_latency_s / self.n_queries if self.n_queries else 0.0

    def p99_latency_ms(self) -> float:
        if not self.latencies:
            return 0.0
        xs = sorted(self.latencies)
        # nearest-rank p99: ceil(0.99 n) - 1.  int(0.99 n) is off by one — it
        # returns the maximum (p100) for every run with <= 100 queries.
        rank = min(len(xs) - 1, max(0, math.ceil(0.99 * len(xs)) - 1))
        return 1e3 * xs[rank]

    @property
    def mean_service_ms(self) -> float:
        return 1e3 * self.sum_service_s / self.n_queries if self.n_queries else 0.0

    @property
    def deadline_hit_rate(self) -> float:
        tot = self.deadline_hits + self.deadline_misses
        return self.deadline_hits / tot if tot else 0.0

    @property
    def ios_per_query(self) -> float:
        return self.io_count / self.n_queries if self.n_queries else 0.0

    @property
    def hit_rate(self) -> float:
        tot = self.cache_hits + self.cache_misses
        return self.cache_hits / tot if tot else 0.0

    @property
    def hbm_hit_rate(self) -> float:
        tot = self.hbm_hits + self.hbm_misses
        return self.hbm_hits / tot if tot else 0.0

    @property
    def requests_per_flush(self) -> float:
        """Mean score ops fused per dispatch (1.0 == no cross-query fusion)."""
        return self.score_requests / self.score_flushes if self.score_flushes else 0.0

    @property
    def rows_per_flush(self) -> float:
        return self.score_rows / self.score_flushes if self.score_flushes else 0.0

    @property
    def downloads_per_query(self) -> float:
        """Host<->device exchanges per query that carried raw distances."""
        return self.dist_downloads / self.n_queries if self.n_queries else 0.0
