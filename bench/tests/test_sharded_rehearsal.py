"""CPU rehearsal of the four-chip cell ``gist1m-sharded4.closed-b256``, and
its three device-trace metrics on a hand-made four-chip trace.

The harness runs the cell end to end on four CPU devices at a tiny size
(widths as configured).  JAX fixes its device count when it starts and the
suite's workers run with one CPU device, so the run is a subprocess of its
own: this file run as a script, printing the result lines.
"""

import json
import os
import subprocess
import sys

import pytest

from bench import harness, trace_reduce as tr
from bench.tests.conftest import BENCH_DIR

CELL = "gist1m-sharded4.closed-b256"
SEED = 2**31 + 12345  # above 32 signed bits: seeds may be that large
TINY = {"config": {"n": 4800, "search": {"chunk": 1024}},
        "cell": {"pool": {"size": 48}, "loop": {"batch": 16, "warmup_calls": 1}}}


def _runs(cache_dir: str) -> dict:
    from bench import index_cache

    index_cache.CACHE_DIR = cache_dir
    return {trace: harness.run_cell(CELL, SEED, 1.0, trace, allow_cpu=True, overrides=TINY,
                                    log=lambda m: None)
            for trace in (False, True)}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    repo = os.path.dirname(BENCH_DIR)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4").strip())
    env["PYTHONPATH"] = os.pathsep.join([repo, os.path.join(repo, "src")])
    cache = str(tmp_path_factory.mktemp("index"))
    p = subprocess.run([sys.executable, os.path.abspath(__file__), cache], env=env,
                       cwd=repo, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    return {"untraced": out["False"], "traced": out["True"]}


def _cell():
    with open(os.path.join(BENCH_DIR, "workloads", f"{CELL}.json")) as f:
        return json.load(f)


def test_cell_end_to_end(runs):
    res = runs["untraced"]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == set(_cell()["end_to_end"])
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 4


def test_cell_traced(runs):
    """The per-layer metrics read only a TPU's device planes: the CPU has
    none, so the traced line leaves them out and does not fail."""
    res = runs["traced"]
    assert res["correct"] is True, res["checks"]
    assert res["metrics"] == {}
    assert res["device"]["window_s"] > 0


# ------------------------------------------------ the readers on a four-chip trace

PLANES = [f"/device:TPU:{i}" for i in range(4)]
HLO = """HloModule jit_sharded_scan, entry_computation_layout={...}

%fused_computation.1 (p: f32[16,40]) -> f32[16,40] {
  ROOT %neg.1 = f32[16,40] negate(%p), metadata={op_name="jit(sharded_scan)/shard_map/velo.shard.merge/neg"}
}

ENTRY %main.9 (a: u8[1201,120]) -> (s32[16,10], f32[16,10]) {
  %binary_ip_pallas.2 = bf16[16,1200] custom-call(%a), metadata={op_name="jit(sharded_scan)/shard_map/velo.scan.stage1/binary_ip"}
  %all-gather.3 = f32[16,40] all-gather(%x), metadata={op_name="jit(sharded_scan)/shard_map/velo.shard.merge/all_gather"}
  %fusion.1 = f32[16,40] fusion(%all-gather.3), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple.4 = (s32[16,10], f32[16,10]) tuple(%fusion.1)
}
"""


def _four_chip_run(ms_per_call, merge_ms=0.5, counters=None):
    """Chip i's program takes ms_per_call[call][i] ms in each call, of which
    its merge ops take ``merge_ms``."""
    E = tr.Event
    mods = {p: [] for p in PLANES}
    ops = {p: [] for p in PLANES}
    t = 1e6
    for call in ms_per_call:
        for p, ms in zip(PLANES, call):
            end = t + ms * 1e6
            mods[p].append(E("jit_sharded_scan(7)", t, end))
            ops[p].append(E("%binary_ip_pallas.2 = bf16[16,1200] custom-call(%a)", t, end - 2e6))
            ops[p].append(E("%all-gather.3 = f32[16,40] all-gather(%x)", end - 2e6,
                            end - 2e6 + merge_ms * 0.6e6))
            ops[p].append(E("fusion.1", end - 1e6, end - 1e6 + merge_ms * 0.4e6))
        t += 100e6
    trace = tr.Trace(ops=ops, modules=mods, spans=[E("bench.window", 0, t + 1e6)])
    c = {"calls": len(ms_per_call), "batch_rows": [256] * len(ms_per_call), "shards": 4,
         "shard_n": 250_000, "scan_d": 960, "scan_rerank": 512, "hlo_text": HLO}
    c.update(counters or {})
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    return harness.Run({}, {}, 0, 0.0, None, c, 0, {"trace": trace}, peaks, {})


def _metric(name):
    return harness.load_module(BENCH_DIR, "metrics", name)


def test_readers_on_four_chips():
    run = _four_chip_run([[10, 10, 10, 10], [10, 12, 10, 8]])
    # least time at B=256, n=250,000, d=960, C=512 (compute bound):
    # (2*256*250000*960 + 2*256*512*960) / 197e12 = 0.62503 ms
    least = (2 * 256 * 250_000 * 960 + 2 * 256 * 512 * 960) / 197e12
    want = 100 * (least / 10e-3 + least / 12e-3) / 2
    assert _metric("sharded_scan_roofline").read(run) == pytest.approx(want)
    # per call: 0 % and 100 * (12 / 10 - 1) = 20 %; the median of the two
    assert _metric("shard_skew").read(run) == pytest.approx(10.0)
    # 0.5 ms of merge ops on each chip in each call
    assert _metric("merge_ms").read(run) == pytest.approx(0.5)


def test_readers_match_calls_by_overlap_across_chips():
    """A call missing on one chip is left out, not paired with another
    call: paired by position, chip 2's third call would meet the second
    call's slow chip 1."""
    from bench import shard_trace

    run = _four_chip_run([[10, 10, 10, 10], [10, 14, 10, 10], [10, 10, 10, 10]])
    tr_ = run.trace["trace"]
    del tr_.modules[PLANES[2]][1]
    assert _metric("shard_skew").read(run) == pytest.approx(0.0)
    lo, hi = tr.window(tr_)
    calls = shard_trace.program_calls(tr_, "sharded_scan", lo, hi)
    assert len(calls) == 2 and [max(c) for c in calls] == pytest.approx([10e-3, 10e-3])


def test_readers_without_a_device_trace():
    run = _four_chip_run([[10, 10, 10, 10]])
    for name in ("sharded_scan_roofline", "merge_ms", "shard_skew"):
        assert _metric(name).read(harness.Run(
            {}, {}, 0, 0.0, None, run.counters, 0, None, run.peaks, {})) is None
    # a program without the merge scope, and a trace of another program
    assert _metric("merge_ms").read(_four_chip_run(
        [[10, 10, 10, 10]], counters={"hlo_text": HLO.replace("velo.shard.merge", "x")})) is None
    other = _four_chip_run([[10, 10, 10, 10]])
    for evs in other.trace["trace"].modules.values():
        for e in evs:
            e.name = "jit_scan_search(3)"
    for name in ("sharded_scan_roofline", "merge_ms", "shard_skew"):
        assert _metric(name).read(other) is None


if __name__ == "__main__":
    res = _runs(sys.argv[1])
    print(json.dumps({str(k): v for k, v in res.items()}), flush=True)
