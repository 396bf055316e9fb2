"""Fused on-device beam step: parity with the host plane, backend
equivalence, masked top-k merge properties, and the ghost-id regression.

The load-bearing contract (docs/beam_step.md): with ``device_beam=True`` the
engine-resident beam — score -> visited mask -> top-k merge -> frontier
selection in ONE engine call per hop — returns bitwise-identical results
(ids, dists, hops) to the host beam for all five algorithms at
B=1/n_workers=1, on every DistanceEngine backend, fuse on and off.

One scoped exception, measured not assumed: velo's cache-aware pivot
(``acc.resident``) reads the simulated clock, so its TRAJECTORY is
timing-dependent whenever charges change — fuse alone already shifts velo's
hops on the pure host plane (no device beam involved).  Under fuse velo's
bar is therefore bitwise ids/dists; hops are compared only on the
charge-identical fuse-off path.  The same scoping applies across shard
counts: S>=2 bitwise parity is asserted for the deterministic-trajectory
algorithms (diskann, inmemory, starling), recall-level for velo.

The ghost-id regression (repro.velo.batch_search._merge_and_trim): a killed
duplicate copy must forfeit its id to the sentinel, not just its distance —
on an underfull beam the (INF, visited) tail survives the trim, and a ghost
keeping a real id would pair with that id's live copy in a LATER merge,
falsely marking it visited via the OR aggregation (and a 3-long id run
would break the pairwise-dedupe assumption).
"""

import numpy as np
import pytest

from repro.core import baselines
from repro.core import beam as beam_mod
from repro.core import dataset as dataset_mod
from repro.core import distance as distance_mod
from repro.core import vamana as vamana_mod
from repro.core.quant import RabitQuantizer
from repro.core.search import ALGORITHMS, SearchParams

ALGOS = sorted(ALGORITHMS)
TIMING_DEPENDENT = {"velo"}
BACKENDS = ["scalar", "batch", "pallas"]


@pytest.fixture(scope="module")
def tiny():
    ds = dataset_mod.make_dataset(n=600, d=32, n_queries=12, k=10, seed=4)
    graph = vamana_mod.build_vamana(ds.base, R=12, L=24, batch_size=256,
                                    seed=4)
    qb = RabitQuantizer(32, seed=4).fit_encode(ds.base)
    return ds, graph, qb


def _run(tiny, algo, device_beam, fuse, backend="batch", n_shards=None,
         batch_size=1, n_workers=1):
    ds, graph, qb = tiny
    cfg = baselines.SystemConfig(
        buffer_ratio=0.2, n_workers=n_workers, batch_size=batch_size,
        fuse=fuse, n_shards=n_shards, device_beam=device_beam,
        distance_backend=backend, params=SearchParams(L=24, W=4),
    )
    sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
    results, stats = sys_.run(ds.queries)
    return sys_, results, stats


def _key(results, with_hops=True):
    return [
        (list(r.ids), list(r.dists), r.hops if with_hops else None)
        for r in results
    ]


def _recall(results, ds):
    ids = np.full((len(results), 10), -1, dtype=np.int64)
    for i, r in enumerate(results):
        m = min(10, len(r.ids))
        ids[i, :m] = r.ids[:m]
    return dataset_mod.recall_at_k(ids, ds.groundtruth, 10)


# ------------------------------------------------- the host-parity contract


@pytest.mark.parametrize("fuse", [False, True], ids=["nofuse", "fuse"])
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("algo", ALGOS)
def test_device_beam_bitwise_parity_with_host(algo, backend, fuse, tiny):
    _, ref, _ = _run(tiny, algo, False, fuse, backend)
    _, got, stats = _run(tiny, algo, True, fuse, backend)
    with_hops = not (fuse and algo in TIMING_DEPENDENT)
    assert _key(got, with_hops) == _key(ref, with_hops), (
        f"{algo}/{backend}/fuse={fuse}: device beam diverged from host"
    )
    assert stats.beam_ops > 0, f"{algo}: beam path never taken"
    assert stats.dist_downloads < _run(
        tiny, algo, False, fuse, backend
    )[2].dist_downloads, f"{algo}: fused steps saved no downloads"


@pytest.mark.parametrize("algo", ALGOS)
def test_device_beam_downloads_collapse(algo, tiny):
    """With the beam on the engine, only the refine stream ships distances
    home: at most ~1.15 downloads a hop, and 0.6 of the host plane's total
    or less (none at all for the in-memory system)."""
    ds, graph, qb = tiny
    per = {}
    for device_beam in (False, True):
        cfg = baselines.SystemConfig(
            buffer_ratio=0.2, n_workers=2, batch_size=4,
            device_beam=device_beam, params=SearchParams(L=24, W=4),
        )
        sys_ = baselines.build_system(algo, ds.base, graph, qb, cfg)
        per[device_beam] = baselines.evaluate(sys_, ds)
    dev, host = per[True], per[False]
    assert dev["beam_ops"] > 0
    assert dev["downloads_per_query"] <= 1.15 * max(dev["mean_hops"], 1e-9)
    assert dev["downloads_per_query"] <= 0.6 * host["downloads_per_query"]


@pytest.mark.parametrize("algo", ALGOS)
def test_device_beam_recall_level_at_interleaved_batch(algo, tiny):
    """B>1 interleaves coroutines, so trajectories may shift; the result
    QUALITY must not (recall within 0.02 of the host plane)."""
    ds = tiny[0]
    _, ref, _ = _run(tiny, algo, False, True, batch_size=4)
    _, got, stats = _run(tiny, algo, True, True, batch_size=4)
    assert abs(_recall(got, ds) - _recall(ref, ds)) <= 0.02
    assert stats.beam_ops > 0


def test_device_beam_off_is_the_default(tiny):
    ds, graph, qb = tiny
    sys_ = baselines.build_system("velo", ds.base, graph, qb,
                                  baselines.SystemConfig())
    _, stats = sys_.run(ds.queries)
    assert sys_.config.device_beam is False
    assert stats.beam_ops == 0


# -------------------------------------------------- sharded-plane parity


@pytest.mark.parametrize("fuse", [False, True], ids=["nofuse", "fuse"])
@pytest.mark.parametrize("algo", ALGOS)
def test_s1_sharded_parity_with_device_beam(algo, fuse, tiny):
    """The degenerate serving plane must not perturb the device beam: S=1
    sharded == unsharded, bitwise, with device_beam on."""
    _, ref, _ = _run(tiny, algo, True, fuse)
    _, got, stats = _run(tiny, algo, True, fuse, n_shards=1)
    assert _key(got) == _key(ref), f"{algo}/fuse={fuse}"
    assert stats.beam_ops > 0 and stats.scatter_ops > 0


@pytest.mark.parametrize("algo", ["diskann", "inmemory", "starling"])
def test_s2_bitwise_for_deterministic_trajectories(algo, tiny):
    """Multi-shard split + local-top-L merge + beam_finalize must reproduce
    the single-shard results exactly for algorithms whose trajectory does
    not read the clock."""
    _, ref, _ = _run(tiny, algo, True, True, n_shards=1)
    for S in (2, 4):
        _, got, stats = _run(tiny, algo, True, True, n_shards=S)
        assert _key(got) == _key(ref), f"{algo} S={S}"
        assert stats.shard_merges > 0, f"{algo} S={S}: no multi-shard merges"


def test_s2_velo_recall_level(tiny):
    ds = tiny[0]
    base = _recall(_run(tiny, "velo", True, True, n_shards=1)[1], ds)
    _, got, stats = _run(tiny, "velo", True, True, n_shards=2)
    assert abs(_recall(got, ds) - base) <= 0.05
    assert stats.shard_merges > 0


# ------------------------------------- backend equivalence, engine level


def _mk_req(qb, pq, state, fresh, explored=(), insert_ids=(), insert_ds=(),
            topk=0):
    fresh = np.asarray(fresh, np.int64)
    return beam_mod.BeamRequest(
        kind="estimate", state=state, fresh=fresh,
        explored=np.asarray(explored, np.int64),
        insert_ids=np.asarray(insert_ids, np.int64),
        insert_ds=np.asarray(insert_ds, np.float32),
        rows=int(fresh.size), flop_s=0.0, pq=pq, qb=qb, topk=int(topk),
    )


@pytest.mark.parametrize("backend", ["batch", "pallas"])
def test_beam_step_backends_match_scalar_oracle(backend):
    """A hostile step sequence — duplicate frontiers, re-submitted visited
    ids, seed inserts, explored marks emptying the frontier — produces
    lane-for-lane identical SELECTIONS on every backend.  Distances agree
    to float32 rounding only (scalar vs vectorized accumulation order);
    the bitwise contract is host-vs-device WITHIN a backend, asserted by
    the system-level parity tests above."""
    rng = np.random.default_rng(3)
    n, d, L = 200, 16, 8
    base = rng.standard_normal((n, d)).astype(np.float32)
    qb = RabitQuantizer(d, seed=0).fit_encode(base)
    pq = RabitQuantizer.prepare_query(
        qb, rng.standard_normal(d).astype(np.float32)
    )
    ref_eng = distance_mod.get_engine("scalar")
    got_eng = distance_mod.get_engine(backend)

    steps = [
        # seed insert + first frontier
        dict(fresh=[0], insert_ids=[0], insert_ds=[0.0], topk=0),
        # duplicates inside one frontier: first-wins
        dict(fresh=[5, 9, 5, 14, 9, 9], topk=0),
        # every id already visited: the step may only apply marks
        dict(fresh=[5, 9, 14], explored=[5], topk=0),
        # a fat frontier (wider than the beam) + a heap readout
        dict(fresh=list(range(20, 60)), explored=[9, 14], topk=L),
    ]
    st_ref = ref_eng.beam_new(L, n)
    st_got = got_eng.beam_new(L, n)
    for i, kw in enumerate(steps):
        (r,) = ref_eng.beam_step_many(qb, [_mk_req(qb, pq, st_ref, **kw)])
        (g,) = got_eng.beam_step_many(qb, [_mk_req(qb, pq, st_got, **kw)])
        np.testing.assert_array_equal(
            np.asarray(g.frontier), np.asarray(r.frontier), f"step {i}"
        )
        assert g.window_len == r.window_len, f"step {i}"
        np.testing.assert_allclose(
            np.float32(g.tail), np.float32(r.tail), rtol=1e-5, atol=1e-6,
            err_msg=f"step {i}",
        )
        if kw["topk"]:
            np.testing.assert_array_equal(
                np.asarray(g.topk_ids), np.asarray(r.topk_ids), f"step {i}"
            )
            np.testing.assert_allclose(
                np.asarray(g.topk_ds, np.float32),
                np.asarray(r.topk_ds, np.float32), rtol=1e-5, atol=1e-6,
                err_msg=f"step {i}",
            )


# ----------------------------------------- masked top-k merge properties


def _oracle_merge(cand, new, L):
    """The host _Beam's insort semantics: sort (d, v) ascending, keep L."""
    merged = sorted(cand + new)[:L]
    pad = [(float(beam_mod.INF), int(beam_mod.PAD_VID))] * (L - len(merged))
    return merged + pad


def test_merge_topk_matches_insort_oracle():
    rng = np.random.default_rng(7)
    for L in (1, 4, 16):
        for trial in range(20):
            n_c = int(rng.integers(0, L + 1))
            n_n = int(rng.integers(0, 2 * L))
            cand = [(float(np.float32(rng.random())), int(v))
                    for v in rng.integers(0, 50, n_c)]
            cand = sorted(cand) + [(float(beam_mod.INF),
                                    int(beam_mod.PAD_VID))] * (L - n_c)
            new = [(float(np.float32(rng.random())), int(v))
                   for v in rng.integers(0, 50, n_n)]
            d, v = beam_mod.merge_topk(
                np.array([c[0] for c in cand], np.float32),
                np.array([c[1] for c in cand], np.int64),
                np.array([x[0] for x in new], np.float32),
                np.array([x[1] for x in new], np.int64), L,
            )
            want = _oracle_merge(
                [c for c in cand if c[1] != beam_mod.PAD_VID], new, L
            )
            got = list(zip([float(x) for x in d], [int(x) for x in v]))
            assert got == want, (L, trial)


def test_merge_topk_padding_never_wins():
    """Pad lanes (INF, PAD_VID) sort strictly after every real candidate —
    even one carrying a genuinely infinite distance."""
    d, v = beam_mod.merge_topk(
        np.full(4, beam_mod.INF, np.float32),
        np.full(4, beam_mod.PAD_VID, np.int64),
        np.array([np.inf, 0.5], np.float32), np.array([3, 9], np.int64), 4,
    )
    assert list(v[:2]) == [9, 3]          # real inf sorts before pads by id
    assert all(x == beam_mod.PAD_VID for x in v[2:])
    assert d[0] == np.float32(0.5) and np.isinf(d[1])


def test_select_frontier_all_explored_and_underfull():
    L, n = 4, 10
    explored = np.zeros(n + 1, dtype=bool)
    cand_d = np.array([0.1, 0.2, beam_mod.INF, beam_mod.INF], np.float32)
    cand_v = np.array([3, 7, beam_mod.PAD_VID, beam_mod.PAD_VID], np.int64)
    front, wlen, tail = beam_mod.select_frontier(cand_d, cand_v, explored)
    assert list(front) == [3, 7] and wlen == 2 and np.isinf(tail)
    explored[[3, 7]] = True
    front, wlen, tail = beam_mod.select_frontier(cand_d, cand_v, explored)
    assert front.size == 0 and wlen == 2   # exhausted, but the window stays


def test_dedupe_first_keeps_first_occurrence():
    keep = beam_mod.dedupe_first(np.array([4, 2, 4, 4, 9, 2]))
    assert list(keep) == [True, True, False, False, True, False]
    assert beam_mod.dedupe_first(np.zeros(0, np.int64)).size == 0


# ------------------------------------------------ the ghost-id regression


def test_merge_and_trim_killed_dup_forfeits_its_id():
    """A killed duplicate must become a sentinel lane, not a ghost keeping
    the real id at (INF, visited): on an underfull beam the ghost survives
    the trim and poisons a later merge's OR(visited) aggregation."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.velo import batch_search

    n, L = 100, 4
    ids = jnp.array([[5, n, n, n]], jnp.int32)
    dist = jnp.array([[0.5, batch_search.INF, batch_search.INF,
                       batch_search.INF]], jnp.float32)
    visited = jnp.array([[False, True, True, True]])
    new_ids = jnp.array([[5, 7]], jnp.int32)       # 5 duplicates the beam
    new_dist = jnp.array([[0.4, 0.6]], jnp.float32)

    out_ids, out_dist, out_vis = batch_search._merge_and_trim(
        ids, dist, visited, new_ids, new_dist, L, n
    )
    oi = np.asarray(out_ids)[0]
    od = np.asarray(out_dist)[0]
    ov = np.asarray(out_vis)[0]
    # id 5 appears ONCE, with the min distance, still unvisited
    assert int((oi == 5).sum()) == 1, f"ghost copy of id 5 survived: {oi}"
    lane = int(np.argmax(oi == 5))
    assert od[lane] == np.float32(0.4) and not ov[lane]
    # the second merge the ghost used to poison: bring in a fresh neighbor
    # and assert the live id-5 lane still is not falsely marked visited
    out2_ids, _, out2_vis = batch_search._merge_and_trim(
        out_ids, out_dist, out_vis,
        jnp.array([[8]], jnp.int32), jnp.array([[0.7]], jnp.float32), L, n,
    )
    oi2 = np.asarray(out2_ids)[0]
    ov2 = np.asarray(out2_vis)[0]
    assert int((oi2 == 5).sum()) == 1
    assert not ov2[int(np.argmax(oi2 == 5))], "live candidate poisoned"
