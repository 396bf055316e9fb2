"""The batched distance plane: backend parity + batch-primitive properties.

The contract under test: every search algorithm, run end-to-end through the
engine, must return the SAME neighbors (ids), hops, and I/O counts whichever
DistanceEngine backend computes its distances — scalar oracle, vectorized
NumPy, or the Pallas kernels in interpret mode — with distances matching to
float tolerance.  This is what makes the backends interchangeable by config.
"""

import numpy as np
import pytest

from repro.core import baselines, distance
from repro.core.quant import RabitQuantizer
from repro.core.search import ALGORITHMS

BACKENDS = ["scalar", "batch", "pallas"]
ALGOS = sorted(ALGORITHMS)  # diskann, inmemory, pipeann, starling, velo

N_QUERIES = 16


def _run_system(name, ds, graph, qb, backend):
    cfg = baselines.SystemConfig(
        buffer_ratio=0.2,
        batch_size=4,
        distance_backend=backend,
        params=baselines.SearchParams(L=32, W=4),
    )
    sys_ = baselines.build_system(name, ds.base, graph, qb, cfg)
    results, _ = sys_.run(ds.queries[:N_QUERIES])
    assert sys_.ctx.dist.name == backend, "requested backend must be active"
    return results


# -------------------------------------------------------- end-to-end parity


@pytest.mark.parametrize("algo", ALGOS)
def test_backend_parity_all_algorithms(algo, small_ds, small_graph, small_qb):
    """scalar == batch == pallas: same ids/hops/reads, dists to tolerance."""
    runs = {
        b: _run_system(algo, small_ds, small_graph, small_qb, b) for b in BACKENDS
    }
    ref = runs["scalar"]
    for backend in ("batch", "pallas"):
        got = runs[backend]
        for i, (r0, r1) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(
                r0.ids, r1.ids, err_msg=f"{algo}/{backend} query {i}: ids"
            )
            assert r0.hops == r1.hops, f"{algo}/{backend} query {i}: hops"
            assert r0.reads == r1.reads, f"{algo}/{backend} query {i}: reads"
            np.testing.assert_allclose(
                r0.dists, r1.dists, rtol=2e-3, atol=2e-3,
                err_msg=f"{algo}/{backend} query {i}: dists",
            )


def test_engine_counts_batches(small_ds, small_graph, small_qb):
    """The plane must be fed batches, not single rows: rows/call > 1."""
    cfg = baselines.SystemConfig(
        buffer_ratio=0.2, batch_size=4, distance_backend="batch"
    )
    sys_ = baselines.build_system("diskann", small_ds.base, small_graph, small_qb, cfg)
    sys_.run(small_ds.queries[:N_QUERIES])
    stats = sys_.ctx.dist.stats
    assert stats.level1_rows > 0 and stats.full_rows > 0
    assert stats.rows_per_call() > 2.0, stats


# ------------------------------------------------- batch primitive properties


@pytest.fixture(scope="module")
def prepared(small_ds, small_qb):
    return RabitQuantizer.prepare_query(small_qb, small_ds.queries[0])


@pytest.mark.parametrize("m", [1, 3, 64, 65, 200])
def test_estimate_batch_shape_dtype(m, small_qb, prepared, rng):
    ids = rng.integers(0, small_qb.norms.shape[0], m)
    out = RabitQuantizer.estimate_batch(
        small_qb, prepared,
        small_qb.binary_codes[ids], small_qb.norms[ids], small_qb.ip_bar[ids],
    )
    assert out.shape == (m,) and out.dtype == np.float32
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("m", [1, 3, 64, 65, 200])
def test_refine_batch_shape_dtype(m, small_qb, prepared, rng):
    ids = rng.integers(0, small_qb.norms.shape[0], m)
    out = RabitQuantizer.refine_batch(
        small_qb, prepared,
        small_qb.ext_codes[ids], small_qb.ext_lo[ids], small_qb.ext_step[ids],
    )
    assert out.shape == (m,) and out.dtype == np.float32
    assert np.all(out >= 0.0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_primitives_match_oracle(backend, small_qb, prepared, rng):
    """estimate/refine/refine_full agree with the scalar oracle row-for-row,
    at every row count a search frontier can produce (incl. bucket edges)."""
    oracle = distance.ScalarEngine()
    eng = distance.get_engine(backend)
    for m in (1, 7, 63, 64, 65, 128):
        ids = rng.integers(0, small_qb.norms.shape[0], m)
        np.testing.assert_allclose(
            eng.estimate(small_qb, prepared, ids),
            oracle.estimate(small_qb, prepared, ids),
            rtol=2e-3, atol=2e-3,
        )
        codes, lo, step = (
            small_qb.ext_codes[ids], small_qb.ext_lo[ids], small_qb.ext_step[ids]
        )
        np.testing.assert_allclose(
            eng.refine(small_qb, prepared, codes, lo, step),
            oracle.refine(small_qb, prepared, codes, lo, step),
            rtol=2e-3, atol=2e-3,
        )
        vecs = rng.standard_normal((m, small_qb.dim)).astype(np.float32)
        q = rng.standard_normal(small_qb.dim).astype(np.float32)
        np.testing.assert_allclose(
            eng.refine_full(q, vecs), oracle.refine_full(q, vecs),
            rtol=1e-4, atol=1e-3,
        )


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_empty_batches(backend, small_qb, prepared):
    eng = distance.get_engine(backend)
    assert eng.estimate(small_qb, prepared, np.empty(0, np.int64)).shape == (0,)
    ncode = small_qb.ext_codes.shape[1]
    out = eng.refine(
        small_qb, prepared,
        np.empty((0, ncode), np.uint8), np.empty(0, np.float32),
        np.empty(0, np.float32),
    )
    assert out.shape == (0,)
    assert eng.refine_full(
        np.zeros(small_qb.dim, np.float32), np.empty((0, small_qb.dim), np.float32)
    ).shape == (0,)
    # empty batches must not be charged as engine calls
    assert eng.stats.level1_calls == 0 and eng.stats.level2_calls == 0


def test_record_matrix_roundtrips_build_arrays(small_ds, small_graph, small_qb):
    """Payloads decoded from on-disk pages must reassemble into exactly the
    build-time code matrices (one index image, two access paths)."""
    from repro.core.store import VeloIndex

    index = VeloIndex(small_ds.base, small_graph, small_qb)
    vids = [0, 17, 555, 1234]
    recs = [
        index.decode_record(v, index.store.read_page(index.page_of(v)))
        for v in vids
    ]
    codes, lo, step = index.record_matrix(recs)
    np.testing.assert_array_equal(codes, small_qb.ext_codes[vids])
    np.testing.assert_allclose(lo, small_qb.ext_lo[vids])
    np.testing.assert_allclose(step, small_qb.ext_step[vids])


def test_record_matrix_ext8(small_ds, small_graph):
    """ext_bits=8 records decode and batch-refine through the same plane
    (the Pallas engine must route 8-bit refinement to the NumPy path)."""
    from repro.core.store import VeloIndex

    qb8 = RabitQuantizer(small_ds.dim, seed=0, ext_bits=8).fit_encode(small_ds.base)
    index = VeloIndex(small_ds.base, small_graph, qb8)
    vids = [0, 7, 321]
    recs = [
        index.decode_record(v, index.store.read_page(index.page_of(v)))
        for v in vids
    ]
    codes, lo, step = index.record_matrix(recs)
    assert codes.shape == (len(vids), small_ds.dim)
    np.testing.assert_array_equal(codes, qb8.ext_codes[vids])
    pq = RabitQuantizer.prepare_query(qb8, small_ds.queries[0])
    ref = RabitQuantizer.refine_dist2(qb8, pq, np.asarray(vids))
    for backend in BACKENDS:
        got = index.refine_records(distance.get_engine(backend), pq, recs)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- selection rules


def test_get_engine_selection_rules():
    assert distance.get_engine("scalar").name == "scalar"
    assert distance.get_engine("batch").name == "batch"
    with pytest.raises(ValueError):
        distance.get_engine("not-a-backend")
    # no silent substitute: the device engine is what "pallas" serves, and
    # no alias resolves to an engine the caller did not name
    assert distance.get_engine("pallas").name == "pallas"
    for alias in ("auto", "default", None):
        with pytest.raises(ValueError):
            distance.get_engine(alias)


def test_system_config_defaults_to_batch_engine(small_ds, small_graph, small_qb):
    sys_ = baselines.build_system(
        "velo", small_ds.base, small_graph, small_qb, baselines.SystemConfig()
    )
    assert sys_.ctx.dist.name == "batch"


def test_system_config_defaults_are_explicit():
    """Every serving option is a plain field whose default is what the
    system runs; no module keeps a process-wide default that could change
    it behind the caller's back."""
    cfg = baselines.SystemConfig()
    assert cfg.fuse is False
    assert cfg.fuse_rows == 256
    assert cfg.shared_rendezvous is False
    assert cfg.overlap_flush is False
    assert cfg.hbm_tier is False
    assert cfg.hbm_slots is None
    assert cfg.device_beam is False
    assert cfg.scheduler == "rr"
    assert cfg.sla_ms is None
    assert cfg.distance_backend == "batch"
    for mod in (baselines, distance):
        hooks = [a for a in dir(mod)
                 if a.lower().lstrip("_").startswith(("set_default", "default"))]
        assert hooks == [], (mod.__name__, hooks)
