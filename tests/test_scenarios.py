"""End-to-end scenario matrix: cross-feature regression net.

Every feature PR so far added its own parity tests, but nothing exercised the
CROSS PRODUCT — fused dispatch on top of the async pool on top of the shared
rendezvous, per backend, per algorithm.  This module sweeps

    {algorithm} x {backend} x {fuse/shared-rendezvous} x {async pool}

on a tiny dataset and asserts, for every cell:

  * a recall floor (the features must compose without wrecking accuracy);
  * zero stat-counter leaks when the run drains: no in-flight read tokens
    left in the engine (``_token_info`` / ``_tokens_by_query``), no LOCKED
    buffer-pool slots, no parked waiters, no undrained pending resumes, and
    latency accounting that adds up query-for-query.

The full algorithm sweep runs on the (default) batch backend; the scalar and
pallas backends run a reduced slice — their numerics are already pinned
bitwise by tests/test_distance.py and tests/test_resident.py, so one fused +
shared + async cell per algorithm family is enough to catch composition
regressions without interpret-mode runtime blowup.
"""

import numpy as np
import pytest

from repro.core import baselines
from repro.core import dataset as dataset_mod
from repro.core import vamana as vamana_mod
from repro.core.bufferpool import SlotState
from repro.core.engine import Engine, EngineConfig
from repro.core.quant import RabitQuantizer
from repro.core.search import ALGORITHMS, SearchParams
from repro.core.sim import SSD

ALGOS = sorted(ALGORITHMS)

# (fuse, shared_rendezvous) — shared requires fuse, so the off/on lattice has
# three valid points
FUSE_MODES = [(False, False), (True, False), (True, True)]

RECALL_FLOOR = {
    "diskann": 0.6,
    "inmemory": 0.8,
    "pipeann": 0.6,
    "starling": 0.6,
    "velo": 0.6,
}


@pytest.fixture(scope="module")
def tiny():
    ds = dataset_mod.make_dataset(n=600, d=32, n_queries=12, k=10, seed=4)
    graph = vamana_mod.build_vamana(ds.base, R=12, L=24, batch_size=256,
                                    seed=4)
    qb = RabitQuantizer(32, seed=4).fit_encode(ds.base)
    return ds, graph, qb


def _run_cell(tiny, algo, backend, fuse, shared, async_load):
    """Build the system and drive the engine DIRECTLY (not System.run) so the
    engine instance stays inspectable for leak checks."""
    ds, graph, qb = tiny
    cfg = baselines.SystemConfig(
        buffer_ratio=0.2,
        n_workers=2,
        batch_size=4,
        distance_backend=backend,
        fuse=fuse,
        shared_rendezvous=shared,
        async_load=async_load,
        params=SearchParams(L=24, W=4),
    )
    sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
    engine = Engine(
        store=sys_.store,
        ssd=SSD(),
        cost=sys_.cost,
        config=EngineConfig(
            n_workers=sys_.config.n_workers,
            batch_size=sys_.batch_size,
            page_size=sys_.config.page_size,
            fuse=sys_.config.fuse,
            fuse_rows=sys_.config.fuse_rows,
            shared_rendezvous=sys_.config.shared_rendezvous,
        ),
        dist=sys_.ctx.dist,
        qb=sys_.ctx.qb,
    )
    results, stats = engine.run(sys_.make_coroutine, ds.queries)
    return sys_, engine, results, stats


def _assert_no_leaks(sys_, engine, results, stats, label):
    # engine: every async read token was either consumed or dropped with its
    # finished query
    assert engine._token_info == {}, f"{label}: leaked read tokens"
    assert engine._tokens_by_query == {}, f"{label}: leaked token owner sets"
    # latency accounting adds up, one entry per query
    assert len(stats.latencies) == stats.n_queries == len(results)
    assert len(stats.latency_qids) == stats.n_queries
    assert sorted(stats.latency_qids) == list(range(stats.n_queries))
    assert abs(sum(stats.latencies) - stats.sum_latency_s) < 1e-9
    # buffer pool (record-pool systems): the run drained — no open LOCKED
    # windows, no parked waiters, no undrained resumes
    pool = getattr(sys_.ctx.accessor, "pool", None)
    if pool is not None:
        assert not (pool.state == SlotState.LOCKED).any(), (
            f"{label}: LOCKED slots leaked past the end of the run"
        )
        assert pool.waiters == {}, f"{label}: waiter lists leaked"
        assert pool.pending_resumes == [], f"{label}: undrained resumes"
        pool.check_invariants()


def _recall(results, ds):
    ids = np.full((len(results), 10), -1, dtype=np.int64)
    for i, r in enumerate(results):
        m = min(10, len(r.ids))
        ids[i, :m] = r.ids[:m]
    return dataset_mod.recall_at_k(ids, ds.groundtruth, 10)


@pytest.mark.parametrize("async_load", [True, False],
                         ids=["async", "syncpool"])
@pytest.mark.parametrize("fuse,shared", FUSE_MODES,
                         ids=["nofuse", "fuse", "fuse+shared"])
@pytest.mark.parametrize("algo", ALGOS)
def test_scenario_matrix_batch_backend(algo, fuse, shared, async_load, tiny):
    ds = tiny[0]
    sys_, engine, results, stats = _run_cell(
        tiny, algo, "batch", fuse, shared, async_load
    )
    label = f"{algo}/batch/fuse={fuse}/shared={shared}/async={async_load}"
    rec = _recall(results, ds)
    assert rec >= RECALL_FLOOR[algo], f"{label}: recall {rec:.3f}"
    _assert_no_leaks(sys_, engine, results, stats, label)
    if fuse:
        assert stats.score_flushes > 0, f"{label}: fusion never flushed"
    else:
        assert stats.score_flushes == 0


@pytest.mark.parametrize("backend", ["scalar", "pallas"])
@pytest.mark.parametrize("algo", ["velo", "diskann"])
def test_scenario_matrix_other_backends(algo, backend, tiny):
    """Reduced slice for the non-default backends: the most feature-loaded
    cell (fused + shared rendezvous + async pool)."""
    ds = tiny[0]
    sys_, engine, results, stats = _run_cell(
        tiny, algo, backend, fuse=True, shared=True, async_load=True
    )
    label = f"{algo}/{backend}/fuse+shared/async"
    rec = _recall(results, ds)
    assert rec >= RECALL_FLOOR[algo], f"{label}: recall {rec:.3f}"
    _assert_no_leaks(sys_, engine, results, stats, label)
    assert stats.score_flushes > 0


@pytest.mark.parametrize("fuse,shared", FUSE_MODES,
                         ids=["nofuse", "fuse", "fuse+shared"])
@pytest.mark.parametrize("algo", ALGOS)
def test_scenario_matrix_verify_protocol_inert(algo, fuse, shared, tiny):
    """The dynamic protocol checker (SystemConfig.verify_protocol) rides the
    same cross-feature lattice bitwise-inertly: per cell, the verified run's
    (ids, dists, hops) match the unverified run exactly, zero violations are
    recorded, and the flush-boundary invariant pass demonstrably ran."""
    ds, graph, qb = tiny

    def run(verify):
        cfg = baselines.SystemConfig(
            buffer_ratio=0.2, n_workers=2, batch_size=4,
            fuse=fuse, shared_rendezvous=shared, async_load=True,
            hbm_tier=(algo == "velo"),  # one cell also crosses the HBM tier
            verify_protocol=verify,
            params=SearchParams(L=24, W=4),
        )
        sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
        results, _stats = sys_.run(ds.queries)
        return sys_, results

    _, ref = run(False)
    sys_v, got = run(True)
    label = f"{algo}/fuse={fuse}/shared={shared}/verify"
    assert [
        (list(r.ids), list(r.dists), r.hops) for r in got
    ] == [
        (list(r.ids), list(r.dists), r.hops) for r in ref
    ], f"{label}: verified run diverged from unverified run"
    assert sys_v.checker is not None, f"{label}: checker never armed"
    sys_v.checker.raise_if_violations()
    assert sys_v.checker.flushes > 0, f"{label}: no flush boundary observed"


@pytest.mark.parametrize("fuse", [False, True], ids=["nofuse", "fuse"])
@pytest.mark.parametrize("algo", ALGOS)
def test_scenario_matrix_sharded_verify_inert(algo, fuse, tiny):
    """The sharded row of the matrix: {n_shards=2} x {fuse} x
    {verify_protocol}.  The protocol checker observes flush boundaries PER
    SHARD (flush_sharded calls at_flush once per shard flush, the fuse-off
    scatter path once per inline dispatch) and stays bitwise inert."""
    ds, graph, qb = tiny

    def run(verify):
        cfg = baselines.SystemConfig(
            buffer_ratio=0.2, n_workers=2, batch_size=4,
            fuse=fuse, async_load=True, n_shards=2,
            verify_protocol=verify,
            params=SearchParams(L=24, W=4),
        )
        sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
        results, stats = sys_.run(ds.queries)
        return sys_, results, stats

    _, ref, ref_stats = run(False)
    sys_v, got, stats = run(True)
    label = f"{algo}/sharded/fuse={fuse}/verify"
    assert [
        (list(r.ids), list(r.dists), r.hops) for r in got
    ] == [
        (list(r.ids), list(r.dists), r.hops) for r in ref
    ], f"{label}: verified run diverged from unverified run"
    rec = _recall(got, ds)
    assert rec >= RECALL_FLOOR[algo], f"{label}: recall {rec:.3f}"
    assert stats.scatter_ops > 0, f"{label}: scatter path never taken"
    assert stats.scatter_ops == ref_stats.scatter_ops, label
    assert sys_v.checker is not None, f"{label}: checker never armed"
    sys_v.checker.raise_if_violations()
    assert sys_v.checker.flushes > 0, f"{label}: no flush boundary observed"


@pytest.mark.parametrize("fuse", [False, True], ids=["nofuse", "fuse"])
@pytest.mark.parametrize("scheduler", ["rr", "sla"])
def test_scenario_matrix_scheduler_verify_inert(scheduler, fuse, tiny):
    """The scheduler row of the matrix: {rr, sla} x {fuse} x
    {verify_protocol}.  With staggered arrivals and deadlines attached the
    protocol checker must stay bitwise inert under EITHER scheduling policy
    (EDF reorders dispatches, which is exactly the traffic the checker's
    transition rules must not perturb), and deadline accounting must agree
    between the verified and unverified runs."""
    from repro.core.scheduling import SlaPlan

    ds, graph, qb = tiny
    n = len(ds.queries)
    arr = np.linspace(0.0, 5e-4, n)  # arrivals staggered inside the run

    def run(verify):
        cfg = baselines.SystemConfig(
            buffer_ratio=0.2, n_workers=2, batch_size=4,
            fuse=fuse, async_load=True,
            scheduler=scheduler, sla_ms=2.0,
            verify_protocol=verify,
            params=SearchParams(L=24, W=4),
        )
        sys_ = baselines.build_system("velo", ds.base, graph, qb, cfg)
        results, stats = sys_.run(
            ds.queries, sla=SlaPlan.build(n, arrivals=arr, sla_ms=2.0)
        )
        return sys_, results, stats

    _, ref, ref_stats = run(False)
    sys_v, got, stats = run(True)
    label = f"velo/{scheduler}/fuse={fuse}/verify"
    assert [
        (list(r.ids), list(r.dists), r.hops) for r in got
    ] == [
        (list(r.ids), list(r.dists), r.hops) for r in ref
    ], f"{label}: verified run diverged from unverified run"
    assert stats.deadline_hits == ref_stats.deadline_hits, label
    assert stats.deadline_misses == ref_stats.deadline_misses, label
    assert stats.coroutine_switches == ref_stats.coroutine_switches, label
    assert stats.latency_qids == ref_stats.latency_qids, label
    assert sys_v.checker is not None, f"{label}: checker never armed"
    sys_v.checker.raise_if_violations()
    assert sys_v.checker.flushes > 0, f"{label}: no flush boundary observed"
