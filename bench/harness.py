"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything specific to a configuration, a cell or a metric is found by name:
``configs/<config>.json``, ``workloads/<cell>.json``, ``runners/<path>.py``
(the configuration's ``path``) and ``metrics/<metric>.py``, all under the
benchmark's root.  Adding a cell, a configuration or a metric adds files and
edits none.

A runner module has ``setup(config, seed)`` returning an object with
``search(q) -> (ids, dist2)`` (host arrays, the call blocked until its
answer is on the host), ``reset_counters()``, ``counters() -> dict``,
``free()`` (drops the program's state) and ``base() -> corpus`` (the float
corpus, regenerated from the seed where the runner does not hold it).

A metric module has ``UNIT`` and ``read(run) -> float | None``; ``None``
leaves the metric out of the line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind the cell needs."""


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: dict
    config: dict
    seed: int
    setup_s: float
    window: object                 # loops.Window
    counters: dict
    compiles_in_window: int
    trace: dict | None             # trace_reduce.reduce(...) + the Trace
    peaks: dict | None
    numbers: dict                  # check.measure(...)


def load_json(root: str, kind: str, name: str) -> dict:
    with open(os.path.join(root, kind, f"{name}.json")) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    path = os.path.join(root, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: dict, over: dict | None) -> dict:
    """``base`` with ``over``'s keys replaced, nested dicts merged."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def devices_for(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return devs


def device_info(devs, chips: int) -> dict:
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks_for(kind: str, allow_missing: bool) -> dict | None:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table and not allow_missing:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table.get(kind)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             root: str = BENCH_DIR, t_start: float | None = None,
             allow_cpu: bool = False, overrides: dict | None = None,
             setup=None, log=print) -> dict:
    """One run; returns the result line's object.  ``overrides`` merges into
    the cell (key ``cell``) and the configuration (key ``config``); ``setup``
    replaces the runner's (the control puts itself in the program's place)."""
    import jax

    from bench import check, gen, loops, trace_reduce
    from bench.spans import span

    t_start = time.time() if t_start is None else t_start
    overrides = overrides or {}
    cell = merge(load_json(root, "workloads", cell_name), overrides.get("cell"))
    config = merge(load_json(root, "configs", cell["config"]), overrides.get("config"))
    devs = devices_for(cell["chips"], allow_cpu)
    peaks = peaks_for(devs[0].device_kind, allow_missing=allow_cpu)

    runner = load_module(root, "runners", config["path"])
    pool = gen.make_queries(seed, config["n"], config["d"], cell["pool"]["size"],
                            cell["pool"]["skew"])
    setup = setup or runner.setup
    state = setup(config, seed)
    warm, window = loops.LOOPS[cell["loop"]["kind"]]
    if cell["loop"].get("warmup") == "replay":
        loops.closed_replay(state.search, pool, cell["loop"], seed, seconds)
        state.free()
        state = setup(config, seed)
    else:
        warm(state.search, pool, cell["loop"], seed)
    search = state.search
    state.reset_counters()
    setup_s = time.time() - t_start
    log(f"[bench] set-up {setup_s:.1f}s; window of {seconds}s")

    trace_dir = os.path.join(CACHE_DIR, "trace", cell_name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with gen.CompileCounter() as cc, span("bench.window"):
            win = window(search, pool, cell["loop"], seed, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    counters = state.counters()
    log(f"[bench] window {win.seconds:.2f}s: {len(win.pool_idx)} requests in "
        f"{len(win.call_s)} calls; generator late by {win.late_s:.4f}s at the end")
    dev = device_info(devs, cell["chips"])

    red = None
    if trace:
        red = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]

    state.free()
    del search
    gc.collect()
    with span("bench.check"):
        numbers = check.measure(state.base(), pool, win)
    ok, checks = check.judge(numbers, config["guarantees"])

    run = Run(cell, config, seed, setup_s, win, counters, cc.compiles, red,
              peaks, numbers)
    names = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for name in names:
        mod = load_module(root, "metrics", name)
        v = mod.read(run)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": mod.UNIT}
    out = {"correct": ok, "attempted": int(len(win.pool_idx)),
           "failed": numbers["bad_answers"],
           "metrics": metrics, "device": dev}
    if red is not None:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.time() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    out = sys.stdout
    try:
        with contextlib.redirect_stdout(sys.stderr):
            res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                           t_start=t_start,
                           log=lambda m: print(m, file=sys.stderr, flush=True))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} {c['op']} {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), file=out, flush=True)
    return 0
