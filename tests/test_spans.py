"""The program's own spans (repro.core.spans) in a profiler trace of a small
served system, and that recording them changes nothing the system computes."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import baselines

CFG = baselines.SystemConfig(
    buffer_ratio=0.2, batch_size=4, n_workers=2, seed=0,
    distance_backend="batch", hbm_tier=True, device_beam=True,
    fuse=True, shared_rendezvous=True,
    params=baselines.SearchParams(L=32, W=4, k=10),
)


def _run(ds, graph, qb, trace_dir=None):
    system = baselines.build_system("velo", ds.base, graph, qb, CFG)
    if trace_dir is None:
        return system.run(ds.queries[:24])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        return system.run(ds.queries[:24])
    finally:
        jax.profiler.stop_trace()


def _spans(trace_dir):
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    out = []
    for thread, line in enumerate(ln for p in ProfileData.from_file(path).planes
                                  if p.name.startswith("/host:") for ln in p.lines):
        out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns, thread, dict(e.stats))
                   for e in line.events if e.name.startswith("velo."))
    return out


@pytest.fixture(scope="module")
def traced(small_ds, small_graph, small_qb, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("spans"))
    plain = _run(small_ds, small_graph, small_qb)
    on = _run(small_ds, small_graph, small_qb, trace_dir=d)
    return plain, on, _spans(d)


def test_span_names_and_attributes(traced):
    _, (results, _), spans = traced
    names = {s[0] for s in spans}
    assert {"velo.engine.run", "velo.search.step", "velo.engine.flush",
            "velo.cache.get", "velo.cache.decode", "velo.cache.hbm",
            "velo.dist.call"} <= names
    assert all(n.count(".") == 2 for n in names)
    runs = [s for s in spans if s[0] == "velo.engine.run"]
    assert len(runs) == 1 and runs[0][4]["queries"] == len(results)
    qids = {s[4]["qid"] for s in spans if s[0] == "velo.search.step"}
    assert qids == set(range(len(results)))
    calls = [s for s in spans if s[0] == "velo.dist.call"]
    assert {"kind", "rows"} <= set(calls[0][4])
    assert {s[4]["kind"] for s in calls} >= {"beam", "refine"}


def _parents(spans, child_prefix, parent_names):
    """For every span named ``child_prefix*``: the names of the spans of
    ``parent_names`` that hold it on its own thread."""
    parents = [p for p in spans if p[0] in parent_names]
    return [{p[0] for p in parents if p[3] == c[3] and p[1] <= c[1] and c[2] <= p[2]}
            for c in spans if c[0].startswith(child_prefix)]


def test_spans_nest_in_their_layers(traced):
    spans = traced[2]
    # every program span runs inside the engine's run
    assert all(ps == {"velo.engine.run"}
               for ps in _parents(spans, "velo.", {"velo.engine.run"}))
    # distance work happens in flushes, never inside a coroutine's step
    dist = _parents(spans, "velo.dist.", {"velo.search.step", "velo.engine.flush"})
    assert dist and all(ps == {"velo.engine.flush"} for ps in dist)
    # cache work happens inside a step or a flush, or in an I/O completion
    # callback the engine applies between steps; never in both a step and a
    # flush (no span is held open across a coroutine's suspension)
    cache = _parents(spans, "velo.cache.", {"velo.search.step", "velo.engine.flush"})
    assert any(ps == {"velo.search.step"} for ps in cache)
    assert any(ps == {"velo.engine.flush"} for ps in cache)
    assert all(len(ps) <= 1 for ps in cache)
    # steps and flushes alternate; neither holds the other
    assert not any(_parents(spans, "velo.search.step", {"velo.engine.flush"}))
    assert not any(_parents(spans, "velo.engine.flush", {"velo.search.step"}))


def test_results_bitwise_with_the_profiler_on(traced):
    (r0, s0), (r1, s1), _ = traced
    assert len(r0) == len(r1)
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.dists, b.dists)
        assert (a.hops, a.reads) == (b.hops, b.reads)
    assert s0.makespan_s == s1.makespan_s
    assert s0.latencies == s1.latencies
    assert (s0.io_count, s0.hbm_hits, s0.beam_flushes, s0.score_rows) == (
        s1.io_count, s1.hbm_hits, s1.beam_flushes, s1.score_rows)


def test_pallas_engine_reads_results_in_fetch_spans(small_ds, small_qb, tmp_path):
    from repro.core import distance
    from repro.core.quant import RabitQuantizer

    eng = distance.get_engine("pallas")
    pqs = [RabitQuantizer.prepare_query(small_qb, q) for q in small_ds.queries[:2]]
    groups = [(pq, np.arange(10)) for pq in pqs]
    plain = eng.estimate_many(small_qb, groups)
    with jax.profiler.trace(str(tmp_path)):
        traced = eng.estimate_many(small_qb, groups)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)
    assert "velo.dist.fetch" in {s[0] for s in _spans(str(tmp_path))}
    # the jitted gather carries its scope into the program's op names
    gather_est, _ = distance._pallas_resident_fns()
    tbl = eng.register_index(small_qb)
    text = gather_est.lower(np.stack([pq.qr for pq in pqs]), tbl.binary_codes, tbl.norms,
                            tbl.ip_bar, np.zeros(64, np.int32),
                            interpret=eng.interpret).as_text(debug_info=True)
    assert "velo.dist.gather_estimate" in text
