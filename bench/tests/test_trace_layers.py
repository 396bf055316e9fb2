"""The layer reduction (``bench/trace_layers.py``) on hand-made events, on a
CPU-compiled ``scan_search``, on a small CPU trace and on a tiny cell."""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace_layers as tl
from bench import trace_reduce as tr
from bench.spans import span

CPU = {"device_plane": "/host:CPU", "ops_line": "tf_XLAPjRtCpuClient"}


def S(name, start, end, thread=1, **attrs):
    return tl.Span(name, float(start), float(end), thread, attrs)


def test_span_self_time_leaves_out_children_on_the_same_thread():
    spans = [S("bench.window", 0, 100), S("velo.engine.run", 10, 90),
             S("velo.search.step", 20, 40), S("velo.cache.get", 25, 30),
             S("velo.engine.flush", 50, 80), S("velo.dist.call", 55, 75),
             S("velo.dist.fetch", 60, 70),
             # another thread: overlaps in time, nests in nothing above
             S("velo.search.step", 30, 60, thread=2)]
    self_s = tl.span_self_times(spans, 0, 100)
    assert self_s == pytest.approx({
        "bench.window": 20e-9, "velo.engine.run": 30e-9,
        "velo.search.step": 15e-9 + 30e-9, "velo.cache.get": 5e-9,
        "velo.engine.flush": 10e-9, "velo.dist.call": 10e-9,
        "velo.dist.fetch": 10e-9})
    assert tl.layer_seconds(self_s, tl.SERVED_LAYERS["engine_sched"]) == pytest.approx(40e-9)
    assert tl.layer_seconds(self_s, ("velo.cache.",)) == pytest.approx(5e-9)
    # clipped to the window like trace_reduce.self_times
    assert tl.span_self_times([S("velo.engine.run", -10, 50)], 0, 100) == pytest.approx(
        {"velo.engine.run": 50e-9})


def test_idle_gaps_go_to_the_innermost_program_span():
    E = tr.Event
    spans = [S("bench.window", 0, 100), S("bench.engine_run", 5, 95),
             S("velo.engine.run", 6, 94), S("velo.search.step", 12, 28),
             S("velo.cache.get", 14, 16), S("velo.dist.fetch", 45, 65)]
    bench_only = [s for s in spans if s.name.startswith("bench.")]
    trace = tr.Trace({"/device:TPU:0": [E("a", 0, 10), E("b", 30, 40), E("c", 70, 75)]},
                     {}, [E(s.name, s.start, s.end) for s in bench_only])
    # gaps 10-30 (mid 20: search.step; cache.get ended at 16), 40-70 (mid 55:
    # dist.fetch), 75-100 (mid 87.5: velo.engine.run)
    assert dict(tl.idle_gaps(trace, spans, 0, 100)) == pytest.approx(
        {"velo.search.step": 20e-9, "velo.dist.fetch": 30e-9, "velo.engine.run": 25e-9})
    # the same charge as trace_reduce.idle_gaps where only bench.* spans exist
    assert dict(tl.idle_gaps(trace, bench_only, 0, 100)) == pytest.approx(
        dict(tr.idle_gaps(trace, 0, 100)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gap_sweep_matches_the_scan_over_spans(seed):
    rnd = random.Random(seed)
    E = tr.Event
    ops = sorted((E("op", s, s + rnd.randint(1, 30))
                  for s in (rnd.randint(0, 5000) for _ in range(300))), key=lambda e: e.start)
    spans = [E("bench.window", 0, 5000)]
    for i in range(400):
        s = rnd.randint(0, 4900)
        spans.append(E(f"velo.s{i % 7}", s, s + rnd.choice([1, 5, 50, 500, 2000])))
    trace = tr.Trace({"/device:TPU:0": ops}, {}, spans)
    gaps = tl.device_gaps(ops, 0, 5000)
    assert tl.charge_gaps(gaps, spans) == pytest.approx(
        dict(tr.idle_gaps(trace, 0, 5000, n=100)))


def test_gap_sweep_is_fast():
    rng = np.random.default_rng(0)
    starts = np.sort(rng.uniform(0, 1e9, 100_000))
    spans = [tl.Span("velo.search.step", float(s), float(s) + 5e3, 1, {}) for s in starts]
    edges = np.sort(rng.uniform(0, 1e9, 20_000))
    gaps = [(float(a), float(b)) for a, b in zip(edges[0::2], edges[1::2])]
    t0 = time.perf_counter()
    tot = tl.charge_gaps(gaps, spans)
    assert time.perf_counter() - t0 < 5.0
    assert sum(tot.values()) == pytest.approx(sum(b - a for a, b in gaps) / 1e9)


def test_device_self_time_by_scope():
    E = tr.Event
    ops = [E("%while.4 = (s32[]) while(...)", 0, 100),
           E("%sort.18 = bf16[8] sort(x)", 10, 70),
           E("%binary_ip_pallas.1 = custom-call(y)", 70, 80),
           E("%fusion.9 = f32[8] fusion(z)", 100, 110),
           E("%fusion.2 = f32[8] fusion(z)", 120, 130),
           E("%fusion.9 = f32[8] fusion(z)", 200, 210)]   # another program's
    trace = tr.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": [
        E("jit_scan_search(7)", 0, 150), E("jit_other(3)", 190, 220)]}, [])
    scopes = {"sort.18": "velo.scan.select", "binary_ip_pallas.1": "velo.scan.stage1",
              "fusion.9": "velo.scan.rerank", "fusion.2": "velo.scan.rerank"}
    secs, calls = tl.scope_seconds(trace, "scan_search", 0, 300, scopes)
    assert calls == 1
    # the while op keeps what its body ops leave: no scope of its own
    assert secs == pytest.approx({"other": 30e-9, "velo.scan.select": 60e-9,
                                  "velo.scan.stage1": 10e-9, "velo.scan.rerank": 20e-9})


def test_scopes_from_the_hlo_of_a_cpu_compiled_scan_search():
    from repro.velo.index import synthetic_specs
    from repro.velo.scan_search import scan_search

    n, d, B = 3000, 64, 8
    q = jax.ShapeDtypeStruct((B, d), jnp.float32)
    text = scan_search.lower(synthetic_specs(n, d, 1), q, k=10, rerank=32,
                             use_kernel=False, chunk=1024).compile().as_text()
    scopes = tl.scopes_from_hlo(text)
    assert set(scopes.values()) == {"velo.scan.stage1", "velo.scan.select",
                                    "velo.scan.rerank"}
    # the scan's top-k sits in select, whatever the backend names it
    topk = [k for k in scopes if "sort" in k or "top" in k.lower()]
    assert topk and {scopes[k] for k in topk} >= {"velo.scan.select"}


def test_scopes_from_hlo_by_hand():
    text = """HloModule jit_scan_search
%fused_computation.1 (p.0: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%p.0, %p.0), metadata={op_name="jit(scan_search)/velo.scan.rerank/mul"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %sort.2 = f32[8]{0} sort(%x), metadata={op_name="jit(scan_search)/while/body/velo.scan.select/sort"}
  ROOT %fusion.1 = f32[8]{0} fusion(%sort.2), kind=kLoop, calls=%fused_computation.1
}
"""
    assert tl.scopes_from_hlo(text) == {"multiply.3": "velo.scan.rerank",
                                        "sort.2": "velo.scan.select",
                                        "fusion.1": "velo.scan.rerank"}


def test_layers_of_a_cpu_trace(tmp_path):
    d = str(tmp_path)

    @jax.jit
    def f(x):
        with jax.named_scope("velo.scan.stage1"):
            y = x @ x.T
        with jax.named_scope("velo.scan.select"):
            v, _ = jax.lax.top_k(y, 4)
        return v.sum()

    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with span("bench.window"):
        for q in range(3):
            with span("bench.engine_run"), tl_span("velo.engine.run", queries=1):
                with tl_span("velo.search.step", qid=q):
                    time.sleep(0.01)
                with tl_span("velo.dist.fetch"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    spans = tl.load_spans(d)
    steps = [s for s in spans if s.name == "velo.search.step"]
    assert [s.attrs.get("qid") for s in steps] == [0, 1, 2]
    out = tl.reduce_layers(tr.load(d, **CPU), spans, queries=3)
    assert out["ms_per_query"]["search_step"] >= 10.0
    assert out["ms_per_query"]["dist_wait"] > 0
    assert out["span_counts"]["velo.engine.run"] == 3
    assert max(dict(out["idle_gaps"]), key=dict(out["idle_gaps"]).get) == "velo.search.step"


def tl_span(name, **attrs):
    from repro.core.spans import span as program_span

    return program_span(name, **attrs)


def test_run_traced_on_a_tiny_served_cell():
    from bench.tests.conftest import TINY

    over = TINY["deep96-graph.zipf-closed"]
    # the NumPy engine, so the CPU run takes seconds; it reads no device
    # results, so it waits for none
    over = {**over, "config": {**over["config"], "serving": {"distance_backend": "batch"}}}
    out = tl.run_traced("deep96-graph.zipf-closed", 2**31 + 7, 1.0, log=lambda m: None,
                        overrides=over, allow_cpu=True)
    assert out["result"]["correct"] is True
    lay = out["layers"]
    assert set(lay["ms_per_query"]) == set(tl.SERVED_LAYERS)
    assert lay["ms_per_query"].pop("dist_wait") == 0
    assert all(v > 0 for v in lay["ms_per_query"].values())
    assert lay["velo_spans_per_query"] > 0
    assert lay["span_counts"]["bench.engine_run"] == lay["span_counts"]["velo.engine.run"]


def test_scan_hlo_names_every_stage():
    from bench.tests.conftest import TINY

    cfg = {"n": 3072, "d": 960, **TINY["gist960-flat.closed-b256"]["config"],
           "search": {"k": 10, "rerank": 512, "use_kernel": False, "chunk": 1024}}
    scopes = tl.scopes_from_hlo(tl.scan_hlo(cfg, 16))
    assert set(scopes.values()) == {"velo.scan.stage1", "velo.scan.select",
                                    "velo.scan.rerank"}
