"""The paper's count-based claims about the disk-resident system, at one
small workload: I/O per query, cache hit rates, access skew, recall, and
bytes per shard.  Modelled seconds (QPS, latency, deadline hit rates) are
not claimed here; the chip benchmark (``bench/``) measures speed.

The workload is 3000 clustered vectors of d=64 with a Vamana graph of
degree 16: big enough that each layout holds tens of pages, so a page cache
and a record pool at the same byte budget differ, and small enough to build
in seconds.
"""

import numpy as np
import pytest

from repro.core import baselines
from repro.core import dataset as dataset_mod
from repro.core import vamana as vamana_mod
from repro.core.dataset import recall_at_k
from repro.core.quant import RabitQuantizer
from repro.core.search import SearchParams


@pytest.fixture(scope="module")
def wl():
    ds = dataset_mod.make_dataset(n=3000, d=64, n_queries=48, k=10, seed=7)
    graph = vamana_mod.build_vamana(ds.base, R=16, L=32, seed=7)
    qb = RabitQuantizer(64, seed=7).fit_encode(ds.base)
    return ds, graph, qb


def _build(wl, name, **cfg):
    ds, graph, qb = wl
    return baselines.build_system(name, ds.base, graph, qb,
                                  baselines.SystemConfig(**cfg))


def _run(wl, name, queries=None, **cfg):
    sys_ = _build(wl, name, **cfg)
    results, stats = sys_.run(wl[0].queries if queries is None else queries)
    return sys_, results, stats


def _recall(results, ds):
    ids = np.full((len(results), 10), -1, dtype=np.int64)
    for i, r in enumerate(results):
        m = min(10, len(r.ids))
        ids[i, :m] = r.ids[:m]
    return recall_at_k(ids, ds.groundtruth, 10)


# ----------------------------------------------------- access skew (Fig. 4)


@pytest.fixture(scope="module")
def access_counts(wl):
    sys_, _, _ = _run(wl, "diskann", buffer_ratio=0.2, batch_size=1,
                      track_access=True, params=SearchParams(L=48, W=4))
    return sys_.ctx.accessor.vertex_counts, sys_.ctx.accessor.page_counts


def _top10_share(counts):
    c = np.sort(counts)[::-1]
    return float(c[: max(1, len(c) // 10)].sum() / max(c.sum(), 1))


@pytest.mark.parametrize("claim", [
    "many_vertices_untouched",
    "far_fewer_pages_untouched",
    "vertex_skew_exceeds_page_skew",
])
def test_access_skew_vertex_vs_page(claim, access_counts):
    """Many vertices are never read while almost every page is: the
    mismatch that motivates caching records instead of pages."""
    v, p = access_counts
    v_untouched = float((v == 0).mean())
    p_untouched = float((p == 0).mean())
    if claim == "many_vertices_untouched":
        assert v_untouched > 0.10, v_untouched
    elif claim == "far_fewer_pages_untouched":
        assert p_untouched < 0.5 * v_untouched, (p_untouched, v_untouched)
    else:
        assert _top10_share(v) > _top10_share(p), (
            _top10_share(v), _top10_share(p))


# ------------------------------------------------ I/O per query (Figs. 13, 14)


def test_record_pool_cuts_io(wl):
    """Breakdown step +async -> +record: at the same byte budget the record
    pool reads fewer pages per query than the page cache."""
    kw = dict(buffer_ratio=0.1, batch_size=8, params=SearchParams(L=48, W=4))
    _, _, page = _run(wl, "+async", **kw)
    _, _, record = _run(wl, "+record", **kw)
    assert record.ios_per_query < page.ios_per_query, (
        record.ios_per_query, page.ios_per_query)


def test_co_placement_cuts_io(wl):
    """tau at its default co-places affine records: fewer reads per query
    than no co-placement (tau=0)."""
    ios = {}
    for tau in (0.0, 1.0):
        _, _, stats = _run(wl, "velo", buffer_ratio=0.1, batch_size=8,
                           tau_scale=tau, params=SearchParams(L=48, W=4))
        ios[tau] = stats.ios_per_query
    assert ios[1.0] < ios[0.0], ios


def test_hit_rate_grows_with_beam_width(wl):
    """Fig. 10: a wide cache-aware beam (W=16) hits the pool more often than
    plain best-first (W=0)."""
    rates = {}
    for W in (0, 16):
        if W == 0:
            params = SearchParams(L=48, W=1, cbs=False, prefetch=False)
        else:
            params = SearchParams(L=48, W=W, cbs=True, prefetch=True,
                                  prefetch_depth=W)
        _, _, stats = _run(wl, "velo", buffer_ratio=0.1, batch_size=8,
                           params=params)
        rates[W] = stats.hit_rate
    assert rates[16] > rates[0], rates


def test_velo_recall_close_to_diskann(wl):
    ds = wl[0]
    rec = {
        name: _recall(_run(wl, name, buffer_ratio=0.2, batch_size=4)[1], ds)
        for name in ("velo", "diskann")
    }
    assert rec["velo"] > rec["diskann"] - 0.08, rec


# -------------------------------------------------- cache hit rates (§3.2)


@pytest.fixture(scope="module")
def page_hit_rates(wl):
    """Hit rate by budget for the page cache under three policies and for
    the record pool at the same bytes, on the beam-search access stream."""
    table = {}
    for ratio in (0.1, 0.2):
        for policy in ("lru", "fifo", "random"):
            _, _, stats = _run(wl, "diskann", buffer_ratio=ratio,
                               page_policy=policy, batch_size=1,
                               params=SearchParams(L=48, W=4))
            table.setdefault(policy, []).append(stats.hit_rate)
    _, _, stats = _run(wl, "+record", buffer_ratio=0.1, batch_size=1,
                       params=SearchParams(L=48, W=4, cbs=False,
                                           prefetch=False))
    table["record-clock"] = [stats.hit_rate]
    return table


def test_page_policies_within_6pts_at_low_budget(page_hit_rates):
    """LRU and FIFO gain little over random eviction at budgets <= 20 %."""
    spread = max(
        abs(page_hit_rates[a][i] - page_hit_rates[b][i])
        for i in range(2)
        for a in ("lru", "fifo", "random")
        for b in ("lru", "fifo", "random")
    )
    assert spread < 0.06, page_hit_rates


def test_record_pool_beats_page_cache_at_10pct(page_hit_rates):
    assert page_hit_rates["record-clock"][0] > page_hit_rates["lru"][0], (
        page_hit_rates)


def test_shared_pool_beats_quarter_pools(wl):
    """One pool shared by 4 workers hits more often than the same bytes
    split into four pools, each serving a quarter of the queries."""
    ds = wl[0]
    params = SearchParams(L=48, W=4)
    _, _, shared = _run(wl, "velo", buffer_ratio=0.2, n_workers=4,
                        batch_size=8, params=params)
    hits = misses = 0
    for i in range(4):
        _, _, st = _run(wl, "velo", queries=ds.queries[i::4],
                        buffer_ratio=0.05, batch_size=8, params=params)
        hits += st.cache_hits
        misses += st.cache_misses
    assert shared.hit_rate >= hits / max(1, hits + misses)


@pytest.fixture(scope="module")
def tier_vs_host(wl):
    """Host-only pool at one slot budget against host + HBM tier splitting
    the same budget in half."""
    ds = wl[0]
    params = SearchParams(L=48, W=4)
    host = _build(wl, "velo", buffer_ratio=0.2, params=params)
    half = _build(wl, "velo", buffer_ratio=0.1, params=params)
    tiered = _build(
        wl, "velo", buffer_ratio=0.1, params=params, hbm_tier=True,
        hbm_slots=host.ctx.accessor.pool.n_slots
        - half.ctx.accessor.pool.n_slots,
    )
    return baselines.evaluate(host, ds), baselines.evaluate(tiered, ds)


def test_tier_combined_hit_rate_beats_host_only(tier_vs_host):
    host, tiered = tier_vs_host
    assert tiered["combined_hit_rate"] > host["hit_rate"], (
        tiered["combined_hit_rate"], host["hit_rate"])


def test_tier_serves_hits_at_one_upload_and_floor(tier_vs_host):
    _, tiered = tier_vs_host
    assert tiered["hbm_hits"] > 0
    assert tiered["dist_uploads"] <= 2
    assert tiered["combined_hit_rate"] >= 0.5


# ---------------------------------------------------------- sharded plane


def test_shard_bytes_balanced_at_4_shards(wl):
    sys_ = _build(wl, "velo", buffer_ratio=0.2, n_workers=4, batch_size=8,
                  fuse=True, fuse_rows=48, n_shards=4,
                  params=SearchParams(L=32, W=4))
    by = sys_.store.shard_bytes(sys_.shard_plan.page_shard)
    assert by.min() / by.max() >= 0.9, by
