"""The trace reduction on a small trace recorded on the CPU, and on
hand-made events."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as tr
from bench.spans import span

# On the CPU the XLA operations run on the host's client threads: point the
# reduction's "device" at them.
CPU = {"device_plane": "/host:CPU", "ops_line": "tf_XLAPjRtCpuClient"}


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def matmul_sum(x):
        return (x @ x.T).sum()

    x = jnp.ones((384, 384))
    matmul_sum(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with span("bench.window"):
        for _ in range(3):
            with span("bench.scan_call"):
                matmul_sum(x).block_until_ready()
            with span("bench.wait_arrival"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    return d


def test_reduce_cpu_trace(cpu_trace):
    red = tr.reduce_dir(cpu_trace, **CPU)
    assert 0.06 < red["window_s"] < 5
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["idle_share"] < 1
    names = [n for n, _ in red["device_ops"]]
    assert any("dot" in n for n in names)
    assert all(s > 0 for _, s in red["device_ops"])
    gaps = dict(red["idle_gaps"])
    # the sleeps are the longest idle stretches, charged to their span
    assert gaps["bench.wait_arrival"] >= 0.05
    assert max(gaps, key=gaps.get) == "bench.wait_arrival"
    lo, hi = tr.window(red["trace"])
    plane = next(iter(red["trace"].ops))
    assert len(tr.named_times(red["trace"].ops[plane], "dot_general", lo, hi)) == 3


def test_union_and_gaps_by_hand():
    E = tr.Event
    ops = [E("a", 0, 10), E("b", 5, 20), E("a", 40, 50), E("c", 90, 120)]
    assert tr.union(ops, 0, 100) == [(0, 20), (40, 50), (90, 100)]
    trace = tr.Trace(
        ops={"/device:TPU:0": ops}, modules={},
        spans=[E("bench.window", 0, 100), E("bench.engine_run", 15, 60),
               E("bench.h2d", 20, 45)])
    red = tr.reduce(trace)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["idle_share"] == pytest.approx(0.6)
    # gap 20-40 (mid 30) inside h2d; gap 50-90 (mid 70) in no span
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.h2d": 20e-9, "host.other": 40e-9})
    assert red["device_ops"][0][0] == "a"


def test_self_time_leaves_out_nested_ops():
    E = tr.Event
    ops = [E("%while.4 = (s32[]) while(...)", 0, 100), E("%sort.18 = bf16[8] sort(x)", 10, 70),
           E("%binary_ip_pallas.1 = f32[8] custom-call(y)", 70, 80), E("fusion.2", 120, 130)]
    assert tr.self_times(ops, 0, 200) == pytest.approx(
        {"while.4": 30e-9, "sort.18": 60e-9, "binary_ip_pallas.1": 10e-9, "fusion.2": 10e-9})


def test_named_times_matches_program_names():
    E = tr.Event
    evs = [E("jit_scan_search(123)", 0, 5), E("scan_search_helper", 5, 6),
           E("jit_other", 6, 9), E("scan_search", 10, 12)]
    assert tr.named_times(evs, "scan_search", 0, 20) == pytest.approx([5e-9, 2e-9])
