"""CPU rehearsal of every cell: the harness end to end at a tiny size of each
configuration (widths as configured), through the runner functions."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import BENCH_DIR, TINY

KEYS = ("correct", "attempted", "failed", "metrics", "device")
SEED = 2**31 + 12345  # above 32 signed bits, as the driver's seeds are


def _run(cell, trace, **kw):
    return harness.run_cell(cell, SEED, 1.0, trace, allow_cpu=True,
                            overrides=TINY.get(cell), log=lambda m: None, **kw)


def _cell(name):
    with open(os.path.join(BENCH_DIR, "workloads", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_end_to_end(cell):
    res = _run(cell, trace=False)
    assert all(k in res for k in KEYS)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == set(_cell(cell)["end_to_end"])
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    json.dumps(res)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_traced(cell):
    """A traced run reports its per-layer metrics; those read from the
    device trace are absent on the CPU, which has no device plane."""
    res = _run(cell, trace=True)
    assert res["correct"] is True, res["checks"]
    on_cpu = {"device_idle_share", "scan_roofline"}
    assert set(res["metrics"]) == set(_cell(cell)["per_layer"]) - on_cpu
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_cli_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "gist960-flat.closed-b256", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_new_cell_config_and_metric_are_files_only(tmp_path):
    """A configuration, a cell and a per-layer metric added as new files run
    with no edit to any existing file of the benchmark."""
    root = tmp_path / "bench"
    for kind in ("configs", "workloads", "runners", "metrics"):
        shutil.copytree(os.path.join(BENCH_DIR, kind), root / kind)
    with open(os.path.join(BENCH_DIR, "configs", "gist960-flat.json")) as f:
        cfg = json.load(f)
    cfg.update(n=2000, d=96)
    cfg["search"]["chunk"] = 512
    (root / "configs" / "deep96-flat.json").write_text(json.dumps(cfg))
    cell = {"config": "deep96-flat", "chips": 1, "why": "test cell",
            "loop": {"kind": "closed", "batch": 8, "replace": True, "warmup_calls": 1},
            "pool": {"size": 32, "skew": 0.0},
            "end_to_end": ["qps", "recall_at_10", "setup_s"],
            "per_layer": ["answers_per_call"]}
    (root / "workloads" / "deep96-flat.closed-b8.json").write_text(json.dumps(cell))
    (root / "metrics" / "answers_per_call.py").write_text(
        'UNIT = "queries"\n\n\ndef read(run):\n'
        '    return len(run.window.pool_idx) / len(run.window.call_s)\n')
    res = harness.run_cell("deep96-flat.closed-b8", SEED, 1.0, False, root=str(root),
                           allow_cpu=True, log=lambda m: None)
    assert res["correct"] is True and set(res["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    res = harness.run_cell("deep96-flat.closed-b8", SEED, 1.0, True, root=str(root),
                           allow_cpu=True, log=lambda m: None)
    assert res["metrics"]["answers_per_call"]["value"] == 8.0
