"""Dry-run machinery: HLO analysis unit tests + one real subprocess cell.

The subprocess is required because the 512-virtual-device flag must be set
before jax initializes (the main test process keeps 1 device)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.launch import hlo_analysis as H

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_trip_count_correction():
    """A 64-iteration scan must be counted 64x (XLA's cost analysis counts 1x)."""

    def f(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=64)
        return h.sum()

    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    c = jax.jit(f).lower(w, x).compile()
    cs = H.cost_stats(c.as_text(), 1)
    expect = 2 * 8 * 128 * 128 * 64
    assert abs(cs["flops_per_device"] - expect) / expect < 0.05


def test_nested_scan_trip_counts():
    def f(w, x):
        def outer(h, _):
            def inner(h2, _):
                return jnp.tanh(h2 @ w), None
            h2, _ = jax.lax.scan(inner, h, None, length=4)
            return h2, None
        h, _ = jax.lax.scan(outer, x, None, length=8)
        return h.sum()

    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    c = jax.jit(f).lower(w, x).compile()
    cs = H.cost_stats(c.as_text(), 1)
    expect = 2 * 4 * 64 * 64 * 4 * 8
    assert abs(cs["flops_per_device"] - expect) / expect < 0.05


def test_shape_bytes():
    assert H._shape_bytes("f32[4,4]") == 64
    assert H._shape_bytes("bf16[2,3]{1,0}") == 12
    assert H._shape_bytes("(f32[2], s8[8])") == 16
    assert H._shape_bytes("pred[10]") == 10


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch import roofline

    pk = roofline.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bw"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    ok = {"status": "ok", "arch": "a", "shape": "s", "multi_pod": False,
          "n_devices": 1,
          "cost": {"flops_per_device": 197e12, "bytes_accessed_per_device": 0},
          "collectives": {"collective_bytes_per_device": 0},
          "memory": {"peak_estimate_bytes": 1}}
    assert roofline.analyze(ok)["t_compute_s"] == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.analyze(dict(ok, device_kind="TPU v9 unknown"))


@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    """Lower+compile one real production cell at 512 virtual devices."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "rwkv6-7b", "--shape", "long_500k"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    path = os.path.join(
        REPO, "src", "repro", "launch", "out", "dryrun",
        "rwkv6-7b__long_500k__pod1.json",
    )
    rec = json.load(open(path))
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 256
    assert rec["cost"]["flops_per_device"] > 0
    assert rec["memory"]["peak_estimate_bytes"] < 16 * 2**30
