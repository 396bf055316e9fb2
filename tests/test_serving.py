"""Multi-tenant serving plane: isolation contract, quotas, and workload mixes.

Contracts (deterministic module — hypothesis-based additions belong in their
own module, the dev container lacks hypothesis):

  * Isolation: a ServingPlane with quotas off and the static pool partition
    is *bitwise identical* (ids, dists, hops, reads, per-tenant cache stats)
    to N isolated single-tenant systems, for all five algorithms — a single
    tenant at B in {1, 8}, and two interleaved tenants at B=1 (the
    deterministic schedule; per-query latencies are excluded, the shared
    SSD's queue residue shifts timing without touching results).
  * Sharing pays under skew: the hot tenant's hit rate with one shared pool
    is at least its static-partition hit rate at the same total bytes.
  * Soft quotas cap slot ownership without breaking pool invariants, and
    quota accounting matches ownership exactly after a full run.
  * Flush/I-O overlap: ``overlap_flush`` is bitwise inert at one worker
    (the existing shared-rendezvous parity contract) and engages at
    multiple workers without moving recall.
  * Stats idempotence: ``evaluate``/``plane.run`` report per-run deltas —
    calling them twice must not double-count cache or dispatch counters.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import baselines
from repro.core import dataset as dataset_mod
from repro.core import vamana as vamana_mod
from repro.core import workload as workload_mod
from repro.core.quant import RabitQuantizer
from repro.core.search import ALGORITHMS, SearchParams
from repro.core.serving import (
    ServingPlane,
    TenantSpec,
    combined_table,
    evaluate_plane,
)

ALGOS = sorted(ALGORITHMS)  # diskann, inmemory, pipeann, starling, velo

# the deterministic configuration the bitwise contracts pin (cf.
# tests/test_sharedpool.py): stride prefetch is the one schedule-sensitive
# piece, so the parity params turn it off
PARITY_PARAMS = SearchParams(L=32, W=4, prefetch=False)


@pytest.fixture(scope="module")
def tenant_data():
    out = []
    for i, n in enumerate((700, 600)):
        ds = dataset_mod.make_dataset(n=n, d=32, n_queries=30, k=10, seed=i)
        graph = vamana_mod.build_vamana(ds.base, R=12, L=24, batch_size=256,
                                        seed=i)
        qb = RabitQuantizer(32, seed=i).fit_encode(ds.base)
        out.append((ds, graph, qb))
    return out


def _spec(tenant_data, i, algo, params=PARITY_PARAMS, name=None):
    ds, graph, qb = tenant_data[i]
    return TenantSpec.from_dataset(name or f"t{i}", ds, graph, qb,
                                   system=algo, params=params)


def _isolated(tenant_data, i, algo, batch_size, n_queries,
              params=PARITY_PARAMS, **cfg_kw):
    ds, graph, qb = tenant_data[i]
    cfg = baselines.SystemConfig(buffer_ratio=0.2, batch_size=batch_size,
                                 params=params, **cfg_kw)
    sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
    results, stats = sys_.run(ds.queries[:n_queries])
    return results, stats


def _assert_bitwise(ref, got, label):
    assert len(ref) == len(got)
    for i, (r0, r1) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(r0.ids, r1.ids, err_msg=f"{label} q{i}: ids")
        np.testing.assert_array_equal(r0.dists, r1.dists,
                                      err_msg=f"{label} q{i}: dists")
        assert r0.hops == r1.hops, f"{label} q{i}: hops"
        assert r0.reads == r1.reads, f"{label} q{i}: reads"


# ------------------------------------------------------- isolation contract


@pytest.mark.parametrize("batch_size", [1, 8])
@pytest.mark.parametrize("algo", ALGOS)
def test_single_tenant_plane_bitwise_equals_isolated(algo, batch_size,
                                                     tenant_data):
    """All the plane machinery — combined store, global vid/page namespaces,
    the combined table's offset ids, per-tenant accounting — must add ZERO
    perturbation: a one-tenant plane is the isolated system, bit for bit."""
    spec = _spec(tenant_data, 0, algo)
    cfg = baselines.SystemConfig(buffer_ratio=0.2, batch_size=batch_size,
                                 params=PARITY_PARAMS)
    plane = ServingPlane([spec], cfg, shared_pool=True)
    wload = workload_mod.uniform_mix([30], 30, seed=0)
    run = plane.run(wload)
    ref, ref_stats = _isolated(tenant_data, 0, algo, batch_size, 30)
    _assert_bitwise(ref, run.tenants[0].results, f"{algo} B={batch_size}")
    ts = run.tenants[0].stats
    assert (ts.cache_hits, ts.cache_misses) == (
        ref_stats.cache_hits, ref_stats.cache_misses
    )


@pytest.mark.parametrize("algo", ALGOS)
def test_two_tenant_partitioned_plane_bitwise_equals_isolated(algo,
                                                              tenant_data):
    """Quotas off + static partition + B=1: interleaving two tenants on one
    engine must not change what each tenant computes — ids, hops, reads and
    per-tenant cache stats all match the two isolated systems exactly."""
    specs = [_spec(tenant_data, 0, algo, name="a"),
             _spec(tenant_data, 1, algo, name="b")]
    cfg = baselines.SystemConfig(buffer_ratio=0.2, batch_size=1,
                                 params=PARITY_PARAMS)
    plane = ServingPlane(specs, cfg, shared_pool=False)
    # 40 arrivals keeps per-tenant counts under the 30-query sets (no wrap)
    wload = workload_mod.uniform_mix([30, 30], 40, seed=3)
    run = plane.run(wload)
    assert plane.pool is None  # static partition: no shared pool instance
    for tid in (0, 1):
        tr = run.tenants[tid]
        ref, ref_stats = _isolated(tenant_data, tid, algo, 1,
                                   tr.stats.n_queries)
        _assert_bitwise(ref, tr.results, f"{algo} tenant{tid}")
        assert (tr.stats.cache_hits, tr.stats.cache_misses) == (
            ref_stats.cache_hits, ref_stats.cache_misses
        )


def test_combined_table_requires_matching_shapes(tenant_data):
    ds, _, qb = tenant_data[0]
    qb8 = dataclasses.replace(qb, ext_bits=8)
    assert combined_table([qb, qb]) is not None
    assert combined_table([qb, qb8]) is None
    tbl = combined_table([qb, tenant_data[1][2]])
    n0 = qb.norms.shape[0]
    np.testing.assert_array_equal(tbl.norms[:n0], qb.norms)
    np.testing.assert_array_equal(tbl.norms[n0:], tenant_data[1][2].norms)


# -------------------------------------------------------- sharing under skew


def test_shared_pool_hot_tenant_hit_rate_beats_partition(tenant_data):
    """The point of sharing: under a zipfian hot-tenant mix the shared pool
    lends cold tenants' slots to the hot one — its hit rate must be at least
    the static-partition hit rate at the same total byte budget, and the
    plane's global hit rate must not fall for it."""
    specs = [_spec(tenant_data, 0, "velo", params=SearchParams(L=32, W=4)),
             _spec(tenant_data, 1, "velo", params=SearchParams(L=32, W=4))]
    cfg = baselines.SystemConfig(buffer_ratio=0.12, n_workers=2, batch_size=4)
    wload = workload_mod.zipfian_mix([30, 30], 120, s=1.8, seed=0)
    hot = int(wload.counts().argmax())
    rates, global_rates = {}, {}
    for shared in (True, False):
        plane = ServingPlane(specs, cfg, shared_pool=shared)
        run = plane.run(wload)
        rates[shared] = run.tenants[hot].stats.hit_rate
        global_rates[shared] = run.stats.hit_rate
        for tr in run.tenants:
            if tr.recall is not None:
                assert tr.recall > 0.6, (tr.name, tr.recall)
    assert rates[True] >= rates[False], rates
    assert global_rates[True] >= global_rates[False], global_rates


def test_cross_tenant_fusion_spans_tenants(tenant_data):
    """With the fused distance plane, one rendezvous flush serves requests
    from DIFFERENT tenants (the combined-table routing)."""
    specs = [_spec(tenant_data, 0, "velo"), _spec(tenant_data, 1, "velo")]
    cfg = baselines.SystemConfig(buffer_ratio=0.2, n_workers=2, batch_size=8,
                                 fuse=True, fuse_rows=128,
                                 shared_rendezvous=True)
    plane = ServingPlane(specs, cfg, shared_pool=True)
    assert plane.table is not None
    run = plane.run(workload_mod.uniform_mix([30, 30], 60, seed=1))
    assert run.stats.cross_tenant_flushes > 0
    # the combined table registers ONCE for the whole plane
    assert plane.dist.stats.uploads == 1


# ------------------------------------------------------------- soft quotas


def test_tenant_quota_caps_ownership_and_keeps_invariants(tenant_data):
    specs = [_spec(tenant_data, 0, "velo", params=SearchParams(L=32, W=4)),
             _spec(tenant_data, 1, "velo", params=SearchParams(L=32, W=4))]
    cfg = baselines.SystemConfig(buffer_ratio=0.12, n_workers=2, batch_size=4,
                                 tenant_quota=0.4)
    plane = ServingPlane(specs, cfg, shared_pool=True)
    wload = workload_mod.zipfian_mix([30, 30], 120, s=1.8, seed=0)
    run = plane.run(wload)
    pool = plane.pool
    pool.check_invariants()
    assert pool.tenant_cap is not None
    assert (pool.tenant_owned <= pool.tenant_cap).all()
    assert run.stats.quota_reclaims > 0  # the cap genuinely bound
    for tr in run.tenants:
        assert tr.recall is None or tr.recall > 0.6


def test_quota_off_is_pure_global_clock(tenant_data):
    """tenant_quota=None must be bit-identical to a pool that never heard of
    tenants: same results, same evictions, zero quota traffic."""
    specs = [_spec(tenant_data, 0, "velo"), _spec(tenant_data, 1, "velo")]
    cfg = baselines.SystemConfig(buffer_ratio=0.12, batch_size=1,
                                 params=PARITY_PARAMS)
    wload = workload_mod.uniform_mix([30, 30], 40, seed=5)
    plane = ServingPlane(specs, cfg, shared_pool=True)
    run = plane.run(wload)
    assert run.stats.quota_reclaims == 0
    assert run.stats.quota_denials == 0
    assert plane.pool.tenant_cap is None
    # ownership accounting still runs (it is bookkeeping, not policy)
    plane.pool.check_invariants()
    assert int(plane.pool.tenant_owned.sum()) == plane.pool.occupancy()


# -------------------------------------------------------- flush/I-O overlap


@pytest.mark.parametrize("algo", ALGOS)
def test_overlap_flush_bitwise_inert_at_one_worker(algo, tenant_data):
    """The ROADMAP follow-on's guard rail: at one worker every due completion
    belongs to the initiator, so the overlap path never engages and the flag
    cannot change results — for all five algorithms, B=8, fused shared
    rendezvous."""
    ds, graph, qb = tenant_data[0]
    outs = {}
    for overlap in (False, True):
        cfg = baselines.SystemConfig(
            buffer_ratio=0.2, n_workers=1, batch_size=8, fuse=True,
            shared_rendezvous=True, overlap_flush=overlap,
            params=PARITY_PARAMS,
        )
        sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
        results, stats = sys_.run(ds.queries)
        outs[overlap] = results
        assert stats.overlap_flushes == 0  # structurally unreachable at 1w
    _assert_bitwise(outs[False], outs[True], f"{algo} overlap@1w")


def test_overlap_flush_engages_at_multiple_workers(tenant_data):
    ds, graph, qb = tenant_data[0]
    recalls = {}
    for overlap in (False, True):
        cfg = baselines.SystemConfig(
            buffer_ratio=0.2, n_workers=4, batch_size=8, fuse=True,
            fuse_rows=512, shared_rendezvous=True, overlap_flush=overlap,
            params=SearchParams(L=48, W=4),
        )
        sys_ = baselines.build_system("velo", ds.base, graph, qb, cfg)
        results, stats = sys_.run(ds.queries)
        if overlap:
            assert stats.overlap_flushes > 0, "overlap never engaged"
        else:
            assert stats.overlap_flushes == 0
        ids = np.full((len(results), 10), -1, dtype=np.int64)
        for i, r in enumerate(results):
            m = min(10, len(r.ids))
            ids[i, :m] = r.ids[:m]
        recalls[overlap] = dataset_mod.recall_at_k(ids, ds.groundtruth, 10)
    assert abs(recalls[True] - recalls[False]) < 0.05, recalls


# -------------------------------------------------------- stats idempotence


def test_evaluate_stats_idempotent(tenant_data):
    """Regression: evaluate() twice on one system used to report CUMULATIVE
    accessor/dispatch counters the second time (double counting).  Counters
    must be per-run deltas."""
    ds, graph, qb = tenant_data[0]
    cfg = baselines.SystemConfig(buffer_ratio=0.2, batch_size=4)
    sys_ = baselines.build_system("velo", ds.base, graph, qb, cfg)
    r1 = baselines.evaluate(sys_, ds)
    r2 = baselines.evaluate(sys_, ds)
    # the table registered during run 1; run 2 must report zero NEW uploads
    assert r1["dist_uploads"] == 1
    assert r2["dist_uploads"] == 0
    # dispatches are per-run, not cumulative (cumulative would be ~2x)
    assert r1["dist_dispatches"] > 0
    assert r2["dist_dispatches"] <= 1.5 * r1["dist_dispatches"]
    # cache counters are per-run deltas: a third run's reported hit rate must
    # equal the delta of the accessor's cumulative counters around that run
    h0, m0 = sys_.ctx.accessor.stats()
    r3 = baselines.evaluate(sys_, ds)
    h1, m1 = sys_.ctx.accessor.stats()
    run3_accesses = (h1 - h0) + (m1 - m0)
    assert run3_accesses > 0
    assert abs(r3["hit_rate"] - (h1 - h0) / run3_accesses) < 1e-12


def test_plane_pressure_counters_not_double_counted(tenant_data):
    """Regression: the engine counts lock_waits/coalesced_record_loads for
    the ops it schedules AND the pool counts them at the slot — the plane
    must report the pool's per-run delta, not the sum of both (2x)."""
    specs = [_spec(tenant_data, 0, "velo", params=SearchParams(L=32, W=4)),
             _spec(tenant_data, 1, "velo", params=SearchParams(L=32, W=4))]
    cfg = baselines.SystemConfig(buffer_ratio=0.2, n_workers=4, batch_size=8)
    plane = ServingPlane(specs, cfg, shared_pool=True)
    run = plane.run(workload_mod.zipfian_mix([30, 30], 80, s=1.4, seed=0))
    assert run.stats.lock_waits == plane.pool.lock_waits
    assert run.stats.coalesced_record_loads == plane.pool.coalesced_record_loads
    assert run.stats.lock_waits > 0  # the regression is observable


def test_plane_run_stats_idempotent(tenant_data):
    specs = [_spec(tenant_data, 0, "velo"), _spec(tenant_data, 1, "velo")]
    cfg = baselines.SystemConfig(buffer_ratio=0.2, batch_size=4)
    plane = ServingPlane(specs, cfg, shared_pool=True)
    wload = workload_mod.uniform_mix([30, 30], 40, seed=2)
    r1 = plane.run(wload)
    r2 = plane.run(wload)
    for a, b in zip(r1.tenants, r2.tenants):
        tot1 = a.stats.cache_hits + a.stats.cache_misses
        tot2 = b.stats.cache_hits + b.stats.cache_misses
        # per-run deltas: the warmed second run counts only ITS accesses
        # (cumulative reporting — the old bug — would be ~2x tot1)
        assert tot2 < 1.5 * tot1, (tot1, tot2)
        assert b.stats.n_queries == a.stats.n_queries


# ------------------------------------------------------ workload generators


def test_workload_generators_deterministic_and_sequential():
    for fn, kw in [
        (workload_mod.uniform_mix, {}),
        (workload_mod.zipfian_mix, {"s": 1.5}),
        (workload_mod.bursty_mix, {"mean_burst": 6}),
    ]:
        w1 = fn([20, 20, 20], 90, seed=7, **kw)
        w2 = fn([20, 20, 20], 90, seed=7, **kw)
        np.testing.assert_array_equal(w1.tenant_ids, w2.tenant_ids)
        np.testing.assert_array_equal(w1.query_ids, w2.query_ids)
        assert len(w1) == 90
        # per-tenant query ids are sequential (wrapping): the isolation
        # contract's precondition
        for t in range(3):
            qs = w1.query_ids[w1.positions(t)]
            np.testing.assert_array_equal(
                qs, np.arange(len(qs), dtype=np.int64) % 20
            )


def test_zipfian_mix_is_skewed_and_bursty_mix_runs():
    counts = workload_mod.zipfian_mix([50] * 4, 400, s=1.6, seed=0).counts()
    assert counts[0] > 2 * counts[-1], counts
    runs = workload_mod.bursty_mix([50] * 4, 400, mean_burst=10, seed=0)
    lens = runs.run_lengths()
    assert float(np.mean(lens)) > 2.5, np.mean(lens)
    uni = workload_mod.uniform_mix([50] * 4, 400, seed=0).run_lengths()
    assert float(np.mean(lens)) > float(np.mean(uni))


def test_evaluate_plane_reports_per_tenant_metrics(tenant_data):
    specs = [_spec(tenant_data, 0, "velo"), _spec(tenant_data, 1, "diskann")]
    cfg = baselines.SystemConfig(buffer_ratio=0.2, batch_size=4)
    plane = ServingPlane(specs, cfg, shared_pool=True)
    res = evaluate_plane(plane, workload_mod.uniform_mix([30, 30], 40, seed=0))
    assert set(res["tenants"]) == {"t0", "t1"}
    for t in res["tenants"].values():
        assert t["recall@k"] > 0.5
        assert 0.0 <= t["hit_rate"] <= 1.0
        assert t["n_queries"] > 0
    # mixed algorithms: diskann forces the shared engine to B=1
    assert plane.batch_size == 1


def test_per_tenant_latency_split_survives_priority_reordering(tenant_data):
    """Under scheduler="sla" queries complete far out of submission order,
    so the per-tenant latency/p99/deadline split must bin by QUERY ID
    (``latency_qids``), never by completion position — a positional zip
    against ``positions()`` silently assigns tenant 0's latencies to
    whichever queries happened to finish first.  Regression for the
    evaluate_plane p99 split."""
    params = SearchParams(L=32, W=4)
    specs = [_spec(tenant_data, 0, "velo", params=params),
             _spec(tenant_data, 1, "velo", params=params)]
    cfg = baselines.SystemConfig(
        buffer_ratio=0.2, n_workers=2, batch_size=4, fuse=True, fuse_rows=64,
        scheduler="sla", sla_ms=[5.0, 1.0], sla_feedback=False,
    )
    plane = ServingPlane(specs, cfg, shared_pool=True)
    wl = workload_mod.bursty_mix([30, 30], 80, mean_burst=8, seed=1,
                                 qps=20000.0)
    run = plane.run(wl)
    stats = run.stats
    # EDF + bursts genuinely reordered completions
    assert stats.latency_qids != sorted(stats.latency_qids)
    assert len(stats.latencies) == len(wl)

    lat_by_qid = dict(zip(stats.latency_qids, stats.latencies))
    svc_by_qid = dict(zip(stats.latency_qids, stats.service_times))
    for tr, tid in zip(run.tenants, (0, 1)):
        pos = list(wl.positions(tid))
        assert list(tr.stats.latency_qids) == pos
        assert tr.stats.latencies == [lat_by_qid[i] for i in pos]
        assert tr.stats.service_times == [svc_by_qid[i] for i in pos]
        # the tenant's p99 comes from its OWN distribution
        lo = 1e3 * min(tr.stats.latencies)
        hi = 1e3 * max(tr.stats.latencies)
        assert lo <= tr.stats.p99_latency_ms() <= hi
        assert (
            tr.stats.deadline_hits + tr.stats.deadline_misses
            == tr.stats.n_queries
        )
    # per-tenant accounting sums back to the global stats
    assert sum(t.stats.deadline_hits for t in run.tenants) == stats.deadline_hits
    assert (
        sum(t.stats.deadline_misses for t in run.tenants)
        == stats.deadline_misses
    )
    assert sum(t.stats.queue_wait_s for t in run.tenants) == pytest.approx(
        stats.queue_wait_s
    )
