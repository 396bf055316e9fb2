"""HBM record-cache tier: residency protocol, zero-upload gathers, parity.

Contracts pinned here:

  * Roundtrip bit-identity: a record served from an HBM slot is
    byte-identical to the on-disk form (`QuantizedBase.record_payload`),
    adjacency included — the tier is a cache, not a re-encoder.
  * Slot gathers score exactly like id gathers on every backend, and the
    pallas slot path never re-uploads payloads (dist_uploads stays O(1)).
  * Tier-off is bitwise inert: `hbm_tier=False` builds no tier and the new
    stats stay zero; tier-on at the deterministic schedule (B=1, cbs off,
    prefetch off) returns identical ids/hops — residency never changes
    *what* is scored, only where the bytes come from.
  * Admission: the pool's publish hook stages only genuine installs; a full
    tier promotes only proven-hot records (promote_after pool hits);
    `peek_split` is non-counting and skips LOCKED slots.
  * Accounting: `evaluate` and `ServingPlane.run` report per-run DELTAS of
    the hbm_* counters (the PR-5 idempotence rule), and the serving plane's
    per-tenant tier split sums to the system-wide count.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import baselines
from repro.core import dataset as dataset_mod
from repro.core import distance as distance_mod
from repro.core import vamana as vamana_mod
from repro.core import workload as workload_mod
from repro.core.bufferpool import RecordBufferPool
from repro.core.hbm import HbmTier
from repro.core.quant import RabitQuantizer
from repro.core.search import SearchParams
from repro.core.sim import CostModel
from repro.core.store import DecodedRecord
from repro.core.serving import ServingPlane, TenantSpec, evaluate_plane

pytest.importorskip("jax")


def _record(qb, v, n):
    return DecodedRecord(
        vid=v, adjacency=np.asarray([(v + 1) % n, (v + 3) % n]),
        ext_payload=qb.record_payload(v),
    )


def _tier_with(qb, vids, n_slots=16):
    n = len(qb.ext_codes)
    tier = HbmTier(qb, np.arange(n) // 4, n_slots=n_slots, R=4)
    for v in vids:
        assert tier._stage(int(v), _record(qb, int(v), n))
    assert tier.scatter_staged() == len(vids)
    return tier


# ---------------------------------------------------------------- roundtrip


def test_lookup_roundtrip_bit_identity(small_qb):
    n = len(small_qb.ext_codes)
    tier = _tier_with(small_qb, [3, 7, 11])
    for v in (3, 7, 11):
        rec = tier.lookup(v)
        assert rec is not None and rec.vid == v
        assert rec.ext_payload == small_qb.record_payload(v)
        np.testing.assert_array_equal(
            rec.adjacency, np.asarray([(v + 1) % n, (v + 3) % n])
        )
    assert tier.lookup(5) is None  # not resident
    assert tier.counters()["hits"] == 3
    assert tier.counters()["misses"] == 1


# -------------------------------------------------------------- slot gathers


@pytest.mark.parametrize("backend", ["scalar", "batch", "pallas"])
def test_refine_slots_matches_refine_ids(small_ds, small_qb, backend):
    eng = distance_mod.get_engine(backend)
    vids = np.asarray([2, 9, 17, 30, 41], dtype=np.int64)
    tier = _tier_with(small_qb, vids)
    slots = tier.cache.record_map[vids].astype(np.int64)
    pq = RabitQuantizer.prepare_query(small_qb, small_ds.queries[0])
    ref = eng.refine_ids(small_qb, pq, vids)
    got = eng.refine_slots(tier, pq, slots)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    pq2 = RabitQuantizer.prepare_query(small_qb, small_ds.queries[1])
    many_ref = eng.refine_ids_many(
        small_qb, [(pq, vids), (pq2, vids[:3])]
    )
    many_got = eng.refine_slots_many(tier, [(pq, slots), (pq2, slots[:3])])
    for r, g in zip(many_ref, many_got):
        np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-6)
    assert eng.stats.slot_gathers > 0


def test_pallas_slot_gather_zero_upload(small_ds, small_qb):
    eng = distance_mod.get_engine("pallas")
    assert eng.name == "pallas" and eng.resident
    vids = np.asarray([1, 5, 9], dtype=np.int64)
    tier = _tier_with(small_qb, vids)
    slots = tier.cache.record_map[vids].astype(np.int64)
    pq = RabitQuantizer.prepare_query(small_qb, small_ds.queries[0])
    eng.register_index(small_qb)
    eng.refine_slots(tier, pq, slots)  # compile + mirror upload
    u0 = eng.stats.uploads
    for qi in range(1, 4):
        pqi = RabitQuantizer.prepare_query(small_qb, small_ds.queries[qi])
        eng.refine_slots(tier, pqi, slots)
    assert eng.stats.uploads == u0, "slot gathers must not re-upload payloads"


# ---------------------------------------------------------------- admission


def test_peek_split_noncounting_and_locked(small_qb):
    from repro.velo.device_cache import LOCKED

    vids = np.asarray([4, 8, 12], dtype=np.int64)
    tier = _tier_with(small_qb, vids)
    c0 = tier.counters()
    ids = np.asarray([4, 6, 8, 12], dtype=np.int64)
    mask, slots = tier.peek_split(ids)
    np.testing.assert_array_equal(mask, [True, False, True, True])
    assert tier.counters() == c0, "peek_split must not count hits/misses"
    # a LOCKED slot (mid-scatter) is excluded from the gather
    tier.cache.slot_state[tier.cache.record_map[8]] = LOCKED
    mask2, slots2 = tier.peek_split(ids)
    np.testing.assert_array_equal(mask2, [True, False, False, True])
    assert len(slots2) == 2
    assert tier.peek_split(np.asarray([6], dtype=np.int64)) is None


def test_on_publish_fires_on_genuine_installs_only(small_qb):
    n = len(small_qb.ext_codes)
    seen = []
    pool = RecordBufferPool(8, np.arange(n) // 4,
                            on_publish=lambda v, r: seen.append(v))
    pool.admit(1, _record(small_qb, 1, n))
    assert seen == [1]
    pool.admit(1, _record(small_qb, 1, n))  # duplicate: keep-first, no hook
    assert seen == [1]
    slot = pool.begin_load(2)
    assert slot >= 0
    pool.finish_load(2, _record(small_qb, 2, n))
    assert seen == [1, 2]


def test_note_hit_promotion_threshold(small_qb):
    n = len(small_qb.ext_codes)
    tier = _tier_with(small_qb, list(range(8)), n_slots=8)  # full
    cold = _record(small_qb, 20, n)
    for _ in range(tier.promote_after - 1):
        tier.note_hit(20, cold)
        assert not tier._staged, "a not-yet-proven record must not stage"
    tier.note_hit(20, cold)
    assert [s[0] for s in tier._staged] == [20], (
        "the promote_after-th pool hit stages the record"
    )
    # cold-tail publications never evict from a full tier
    tier.scatter_staged()
    tier.note_publish(30, _record(small_qb, 30, n))
    assert not tier._staged


# ------------------------------------------------------------ engine parity


def _small_system(ds, graph, qb, hbm, **kw):
    cfg = baselines.SystemConfig(
        buffer_ratio=0.15, distance_backend="batch", hbm_tier=hbm, **kw
    )
    return baselines.build_system("velo", ds.base, graph, qb, cfg)


def test_tier_off_builds_nothing(small_ds, small_graph, small_qb):
    sys_ = _small_system(small_ds, small_graph, small_qb, hbm=False)
    assert sys_.hbm is None
    assert sys_.ctx.accessor.hbm is None
    assert sys_.ctx.accessor.pool.on_publish is None
    res = baselines.evaluate(sys_, small_ds)
    assert res["hbm_tier"] is False
    assert res["hbm_hits"] == res["hbm_scatters"] == res["hbm_evictions"] == 0
    assert res["combined_hit_rate"] == res["hit_rate"]


def test_tier_on_search_parity_deterministic(small_ds, small_graph, small_qb):
    """At the deterministic schedule (B=1, cbs/prefetch off) the tier moves
    bytes, not decisions: ids and hops are identical with the tier on."""
    params = SearchParams(L=32, W=4, cbs=False, prefetch=False)
    off = _small_system(small_ds, small_graph, small_qb, hbm=False,
                        batch_size=1, params=params)
    on = _small_system(small_ds, small_graph, small_qb, hbm=True,
                       batch_size=1, params=params)
    res_off, st_off = off.run(small_ds.queries)
    res_on, st_on = on.run(small_ds.queries)
    for i, (a, b) in enumerate(zip(res_off, res_on)):
        np.testing.assert_array_equal(a.ids, b.ids, err_msg=f"q{i} ids")
        assert a.hops == b.hops, f"q{i} hops"
    assert st_on.hbm_hits > 0


def test_engine_tier_counters_and_uploads(small_ds, small_graph, small_qb):
    sys_ = _small_system(small_ds, small_graph, small_qb, hbm=True)
    res = baselines.evaluate(sys_, small_ds)
    assert res["hbm_tier"] is True
    assert res["hbm_hits"] > 0
    assert res["hbm_scatters"] > 0
    assert res["dist_uploads"] <= 2
    assert sys_.ctx.dist.stats.slot_gathers > 0
    assert res["combined_hit_rate"] >= res["hit_rate"]
    assert res["memory_bytes"] > sys_.index.resident_bytes()


def test_evaluate_reports_per_run_deltas(small_ds, small_graph, small_qb):
    """Satellite regression: hbm_* counters are snapshotted per run — a
    second evaluate reports that run's own tier traffic, not the cumulative
    totals (and a no-traffic run would report zeros)."""
    sys_ = _small_system(small_ds, small_graph, small_qb, hbm=True)
    baselines.evaluate(sys_, small_ds)
    c1 = sys_.hbm.counters()
    assert c1["hits"] > 0
    res2 = baselines.evaluate(sys_, small_ds)
    c2 = sys_.hbm.counters()
    assert res2["hbm_hits"] == c2["hits"] - c1["hits"]
    assert res2["hbm_misses"] == c2["misses"] - c1["misses"]
    assert res2["hbm_scatters"] == c2["scatters"] - c1["scatters"]
    assert res2["hbm_evictions"] == c2["evictions"] - c1["evictions"]
    assert res2["hbm_hits"] < c2["hits"], "delta, not the cumulative total"


# ---------------------------------------------------------------- cost model


def test_fused_batch_s_kind_routing():
    cost = CostModel(batch_dispatch_s=1e-6, full_dispatch_s=9e-6)
    assert cost.fused_batch_s(2e-6, kind="full") == pytest.approx(11e-6)
    assert cost.fused_batch_s(2e-6, kind="quant") == pytest.approx(3e-6)
    assert cost.fused_batch_s(2e-6) == pytest.approx(3e-6)
    # parity default: the full dispatch equals the batch dispatch, so
    # pre-existing full-path charges are bitwise unchanged
    d = CostModel()
    assert d.full_dispatch_s == d.batch_dispatch_s


# -------------------------------------------------------------- serving plane


@pytest.fixture(scope="module")
def hbm_tenants():
    out = []
    for i, n in enumerate((700, 600)):
        ds = dataset_mod.make_dataset(n=n, d=32, n_queries=30, k=10, seed=i)
        graph = vamana_mod.build_vamana(ds.base, R=12, L=24, batch_size=256,
                                        seed=i)
        qb = RabitQuantizer(32, seed=i).fit_encode(ds.base)
        out.append(TenantSpec.from_dataset(f"t{i}", ds, graph, qb,
                                           system="velo"))
    return out


def test_serving_plane_tier_split(hbm_tenants):
    cfg = baselines.SystemConfig(buffer_ratio=0.15, hbm_tier=True,
                                 distance_backend="batch")
    plane = ServingPlane(hbm_tenants, config=cfg, shared_pool=True)
    assert plane.hbm is not None
    wl = workload_mod.zipfian_mix([30, 30], n_ops=60, seed=0)
    out = evaluate_plane(plane, wl)
    assert out["hbm_tier"] is True
    assert out["hbm_hits"] > 0
    per_tenant = sum(t["hbm_hits"] for t in out["tenants"].values())
    assert per_tenant == out["hbm_hits"], "tenant tier split must sum exactly"
    # per-run delta idempotence on the plane (PR-5 counter rule)
    c1 = plane.hbm.counters()
    out2 = evaluate_plane(plane, wl)
    c2 = plane.hbm.counters()
    assert out2["hbm_hits"] == c2["hits"] - c1["hits"]
    per_tenant2 = sum(t["hbm_hits"] for t in out2["tenants"].values())
    assert per_tenant2 == out2["hbm_hits"]


def test_serving_static_partition_gets_no_tier(hbm_tenants):
    cfg = baselines.SystemConfig(buffer_ratio=0.15, hbm_tier=True,
                                 distance_backend="batch")
    plane = ServingPlane(hbm_tenants, config=cfg, shared_pool=False)
    assert plane.hbm is None
    wl = workload_mod.uniform_mix([30, 30], n_ops=40, seed=1)
    out = evaluate_plane(plane, wl)
    assert out["hbm_tier"] is False
    assert out["hbm_hits"] == 0
