"""Resident code plane + shared rendezvous: parity and accounting.

The contracts under test (deterministic module — any hypothesis-based
additions belong in their own module, the dev container lacks hypothesis):

  * register-once tables: ``DistanceEngine.register_index`` uploads an
    index's code tables exactly once per engine; every id-based call after
    that gathers from the registered table (``DistanceStats.uploads`` is
    O(1) per index, where the legacy pallas path re-uploaded gathered rows
    per call).
  * resident == host-gather, bitwise: id-based estimates/refinements served
    from the registered tables equal the caller-gathered matrix path bit for
    bit, at the primitive level and end-to-end for all five algorithms on
    all three backends.
  * shared rendezvous == per-worker rendezvous, bitwise, on a one-worker
    system (any B): the flush points and charges coincide, so the topology
    flag cannot change results; at multiple workers it keeps recall while
    cutting dispatches (the system-wide fused batch).
  * the pallas pad-to-bucket helper handles row counts on a bucket multiple
    (pass-through) and m=0 (pads up to one full bucket).
"""

import numpy as np
import pytest

from repro.core import baselines, distance
from repro.core.dataset import recall_at_k
from repro.core.quant import RabitQuantizer
from repro.core.search import ALGORITHMS

BACKENDS = ["scalar", "batch", "pallas"]
ALGOS = sorted(ALGORITHMS)  # diskann, inmemory, pipeann, starling, velo
N_QUERIES = 16


def _run(name, ds, graph, qb, **kw):
    kw.setdefault("params", baselines.SearchParams(L=32, W=4))
    cfg = baselines.SystemConfig(buffer_ratio=0.2, **kw)
    sys_ = baselines.build_system(name, ds.base, graph, qb, cfg)
    results, stats = sys_.run(ds.queries[:N_QUERIES])
    return sys_, results, stats


def _assert_bitwise(ref, got, label):
    for i, (r0, r1) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(r0.ids, r1.ids, err_msg=f"{label} q{i}: ids")
        assert r0.hops == r1.hops, f"{label} q{i}: hops"
        assert r0.reads == r1.reads, f"{label} q{i}: reads"
        np.testing.assert_array_equal(
            r0.dists, r1.dists, err_msg=f"{label} q{i}: dists"
        )


@pytest.fixture(scope="module")
def prepared(small_ds, small_qb):
    return RabitQuantizer.prepare_query(small_qb, small_ds.queries[0])


# ------------------------------------------------ register-once table uploads


@pytest.mark.parametrize("backend", BACKENDS)
def test_uploads_are_o1_per_index(backend, small_qb, prepared, rng):
    """Many id-based calls, one table upload (the resident-plane invariant)."""
    eng = distance.get_engine(backend)
    for _ in range(12):
        ids = rng.integers(0, small_qb.norms.shape[0], 33)
        eng.estimate(small_qb, prepared, ids)
        eng.refine_ids(small_qb, prepared, ids)
    assert eng.stats.uploads == 1, eng.stats
    assert eng.stats.resident_gathers == 12 * 2 * 33
    # re-registration is idempotent and free
    eng.register_index(small_qb)
    assert eng.stats.uploads == 1


def test_legacy_pallas_uploads_per_call(small_qb, prepared, rng):
    """resident=False keeps the PR-2 behavior the counter was built to expose:
    every kernel call re-uploads its gathered rows."""
    eng = distance.get_engine("pallas", resident=False)
    if eng.name != "pallas":  # pragma: no cover - jax missing
        pytest.skip("pallas unavailable")
    n_calls = 5
    for _ in range(n_calls):
        ids = rng.integers(0, small_qb.norms.shape[0], 17)
        eng.estimate(small_qb, prepared, ids)
    # one host-view registration + one row upload per kernel call
    assert eng.stats.uploads == 1 + n_calls, eng.stats


def test_distinct_indexes_register_separately(small_ds, small_qb, prepared):
    eng = distance.get_engine("batch")
    qb2 = RabitQuantizer(small_ds.dim, seed=7).fit_encode(small_ds.base)
    pq2 = RabitQuantizer.prepare_query(qb2, small_ds.queries[0])
    ids = np.arange(10)
    eng.estimate(small_qb, prepared, ids)
    eng.estimate(qb2, pq2, ids)
    eng.estimate(small_qb, prepared, ids)
    assert eng.stats.uploads == 2


# ------------------------------------- resident == host-gather (primitives)


@pytest.mark.parametrize("backend", BACKENDS)
def test_resident_gather_bitwise_equals_host_gather(
    backend, small_qb, prepared, rng
):
    """Id-based calls against the registered table must equal the
    caller-gathered matrix path BIT FOR BIT on every backend (the pallas
    on-device gather feeds the same kernel the same rows)."""
    eng = distance.get_engine(backend)
    for m in (1, 7, 64, 65, 200):
        ids = rng.integers(0, small_qb.norms.shape[0], m)
        est = eng.estimate(small_qb, prepared, ids)
        ref_est = eng._estimate(
            small_qb, prepared,
            small_qb.binary_codes[ids], small_qb.norms[ids],
            small_qb.ip_bar[ids],
        )
        np.testing.assert_array_equal(est, np.asarray(ref_est, np.float32))
        got = eng.refine_ids(small_qb, prepared, ids)
        ref = eng.refine(
            small_qb, prepared,
            small_qb.ext_codes[ids], small_qb.ext_lo[ids],
            small_qb.ext_step[ids],
        )
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("backend", BACKENDS)
def test_refine_ids_matches_oracle(backend, small_qb, prepared, rng):
    oracle = distance.ScalarEngine()
    eng = distance.get_engine(backend)
    ids = rng.integers(0, small_qb.norms.shape[0], 50)
    np.testing.assert_allclose(
        eng.refine_ids(small_qb, prepared, ids),
        oracle.refine_ids(small_qb, prepared, ids),
        rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_refine_ids_many_matches_per_query(backend, small_ds, small_qb, rng):
    eng = distance.get_engine(backend)
    pqs = [
        RabitQuantizer.prepare_query(small_qb, small_ds.queries[i])
        for i in range(3)
    ]
    groups = [
        (pq, rng.integers(0, small_qb.norms.shape[0], m))
        for pq, m in zip(pqs, (5, 64, 31))
    ]
    fused = eng.refine_ids_many(small_qb, groups)
    single = distance.get_engine(backend)
    for (pq, ids), got in zip(groups, fused):
        np.testing.assert_allclose(
            got, single.refine_ids(small_qb, pq, ids), rtol=2e-3, atol=2e-3
        )
    assert eng.stats.uploads == 1


def test_refine_ids_empty_and_ext8(small_ds, small_graph, prepared, small_qb):
    """Empty id sets are not charged; ext_bits=8 routes to the NumPy path on
    every backend (no int4 kernel) while staying id-addressable."""
    for backend in BACKENDS:
        eng = distance.get_engine(backend)
        out = eng.refine_ids(small_qb, prepared, np.empty(0, np.int64))
        assert out.shape == (0,) and eng.stats.level2_calls == 0
    qb8 = RabitQuantizer(small_ds.dim, seed=0, ext_bits=8).fit_encode(small_ds.base)
    pq8 = RabitQuantizer.prepare_query(qb8, small_ds.queries[0])
    ids = np.asarray([0, 7, 321])
    ref = RabitQuantizer.refine_dist2(qb8, pq8, ids)
    for backend in BACKENDS:
        got = distance.get_engine(backend).refine_ids(qb8, pq8, ids)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# ----------------------------------------- resident == host-gather (end-to-end)


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_resident_plane_parity_end_to_end(
    algo, backend, small_ds, small_graph, small_qb
):
    """All five algorithms, all three backends: the id-based resident wire
    format returns identical ids/hops/reads/dists to the materialized
    host-gather path (the on-disk payloads round-trip to the build tables)."""
    _, ref, _ = _run(
        algo, small_ds, small_graph, small_qb,
        batch_size=4, distance_backend=backend, resident_plane=False,
    )
    sys_, got, _ = _run(
        algo, small_ds, small_graph, small_qb,
        batch_size=4, distance_backend=backend, resident_plane=True,
    )
    _assert_bitwise(ref, got, f"{algo}/{backend}")
    # the resident run registered its index exactly once
    assert sys_.ctx.dist.stats.uploads <= 1


def test_end_to_end_uploads_o1_on_pallas(small_ds, small_graph, small_qb):
    """The acceptance criterion in one test: a whole velo workload on the
    pallas backend uploads tables once, where the host-gather path pays one
    row upload per kernel dispatch (O(hops))."""
    res, _, _ = _run(
        "velo", small_ds, small_graph, small_qb,
        batch_size=4, distance_backend="pallas", resident_plane=True,
    )
    leg, _, _ = _run(
        "velo", small_ds, small_graph, small_qb,
        batch_size=4, distance_backend="pallas", resident_plane=False,
    )
    if res.ctx.dist.name != "pallas":  # pragma: no cover - jax missing
        pytest.skip("pallas unavailable")
    assert res.ctx.dist.stats.uploads == 1
    assert leg.ctx.dist.stats.uploads > 100  # one per dispatch
    assert res.ctx.dist.stats.resident_gathers > 0


# --------------------------------------------------------- shared rendezvous


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("B", [1, 8])
def test_shared_rendezvous_bitwise_one_worker(
    algo, B, small_ds, small_graph, small_qb
):
    """One worker: the shared topology's flush points and charges coincide
    with the per-worker buffer, so results are bitwise identical at any B."""
    _, ref, _ = _run(
        algo, small_ds, small_graph, small_qb,
        batch_size=B, n_workers=1, fuse=True, shared_rendezvous=False,
    )
    _, got, _ = _run(
        algo, small_ds, small_graph, small_qb,
        batch_size=B, n_workers=1, fuse=True, shared_rendezvous=True,
    )
    _assert_bitwise(ref, got, f"{algo} B={B}")


def test_shared_rendezvous_fuses_across_workers(small_ds, small_graph, small_qb):
    """4 workers: the system-wide buffer produces fewer, wider dispatches
    than per-worker fusion at recall parity."""
    s_pw, r_pw, st_pw = _run(
        "velo", small_ds, small_graph, small_qb,
        batch_size=8, n_workers=4, fuse=True, shared_rendezvous=False,
    )
    s_sh, r_sh, st_sh = _run(
        "velo", small_ds, small_graph, small_qb,
        batch_size=8, n_workers=4, fuse=True, shared_rendezvous=True,
    )
    assert s_sh.ctx.dist.stats.dispatches() < s_pw.ctx.dist.stats.dispatches()
    assert st_sh.requests_per_flush > st_pw.requests_per_flush

    def rec(rs):
        ids = np.full((len(rs), 10), -1, np.int64)
        for i, r in enumerate(rs):
            ids[i, : min(10, len(r.ids))] = r.ids[:10]
        return recall_at_k(ids, small_ds.groundtruth[:N_QUERIES], 10)

    assert abs(rec(r_sh) - rec(r_pw)) < 0.1


@pytest.mark.parametrize("algo", ALGOS)
def test_shared_rendezvous_terminates_multi_worker(
    algo, small_ds, small_graph, small_qb
):
    """All five algorithms complete under the shared topology at 2 workers
    (the all-stalled flush is always reachable — no cross-worker deadlock)
    and return a full result set."""
    _, got, stats = _run(
        algo, small_ds, small_graph, small_qb,
        batch_size=4, n_workers=2, fuse=True, shared_rendezvous=True,
    )
    assert len(got) == N_QUERIES and all(r is not None for r in got)
    assert all(len(r.ids) > 0 for r in got)
    assert stats.score_requests > 0


def test_shared_rendezvous_off_is_default(small_ds, small_graph, small_qb):
    """SystemConfig.shared_rendezvous defaults to False: PR-2 per-worker
    semantics unless explicitly enabled."""
    sys_, _, _ = _run("velo", small_ds, small_graph, small_qb, fuse=True)
    assert sys_.config.shared_rendezvous is False


# ------------------------------------------------------ pad-to-bucket helper


def _pallas_engine():
    eng = distance.get_engine("pallas")
    if eng.name != "pallas":  # pragma: no cover - jax missing
        pytest.skip("pallas unavailable")
    return eng


def test_pad_to_bucket_passthrough_on_multiple():
    """m exactly on a bucket multiple: arrays pass through unpadded."""
    eng = _pallas_engine()
    codes = np.arange(eng.bucket * 2 * 8, dtype=np.uint8).reshape(-1, 8)
    norms = np.ones(eng.bucket * 2, dtype=np.float32)
    m, (c, n) = eng._pad_to_bucket([codes, norms], [0, 0])
    assert m == eng.bucket * 2
    assert c is codes and n is norms  # no copy, no pad


def test_pad_to_bucket_pads_and_fills():
    eng = _pallas_engine()
    codes = np.full((5, 4), 9, dtype=np.uint8)
    step = np.full(5, 2.0, dtype=np.float32)
    m, (c, s) = eng._pad_to_bucket([codes, step], [0, 1])
    assert m == 5 and c.shape == (eng.bucket, 4) and s.shape == (eng.bucket,)
    np.testing.assert_array_equal(c[:5], codes)
    assert (c[5:] == 0).all() and (s[5:] == 1.0).all()
    np.testing.assert_array_equal(s[:5], step)


def test_pad_to_bucket_empty_rows():
    """m=0 pads up to one full bucket (a valid static kernel shape)."""
    eng = _pallas_engine()
    codes = np.empty((0, 8), dtype=np.uint8)
    lo = np.empty(0, dtype=np.float32)
    m, (c, lo_p) = eng._pad_to_bucket([codes, lo], [0, 0])
    assert m == 0 and c.shape == (eng.bucket, 8) and lo_p.shape == (eng.bucket,)
    assert (c == 0).all()
    # and the id variant
    m, idsp = eng._pad_ids(np.empty(0, dtype=np.int64))
    assert m == 0 and idsp.shape == (eng.bucket,) and idsp.dtype == np.int32


def test_pad_ids_on_bucket_multiple():
    eng = _pallas_engine()
    ids = np.arange(eng.bucket, dtype=np.int64)
    m, idsp = eng._pad_ids(ids)
    assert m == eng.bucket and idsp.shape == (eng.bucket,)
    np.testing.assert_array_equal(idsp, ids.astype(np.int32))


# ------------------------------------------------------------ cost plumbing


def test_table_upload_charged_once(small_ds, small_graph, small_qb):
    """The engine charges table_upload_s exactly once per run: zeroing it
    shortens the makespan by at most one upload, not one per hop."""
    from repro.core.sim import CostModel

    cfg = baselines.SystemConfig(
        buffer_ratio=0.2, batch_size=4,
        params=baselines.SearchParams(L=32, W=4),
    )
    big = 1e-3
    sys_a = baselines.build_system(
        "velo", small_ds.base, small_graph, small_qb, cfg,
        cost=CostModel(table_upload_s=big),
    )
    _, st_a = sys_a.run(small_ds.queries[:N_QUERIES])
    sys_b = baselines.build_system(
        "velo", small_ds.base, small_graph, small_qb, cfg,
        cost=CostModel(table_upload_s=0.0),
    )
    _, st_b = sys_b.run(small_ds.queries[:N_QUERIES])
    delta = st_a.makespan_s - st_b.makespan_s
    assert 0.0 < delta <= big * 1.5, delta
