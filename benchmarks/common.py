"""Shared benchmark context: datasets + graphs + quantizers, built once and
cached on disk (Vamana construction is the expensive step)."""

from __future__ import annotations

import os
import pickle
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core import baselines as baselines_mod  # noqa: E402
from repro.core import dataset as dataset_mod  # noqa: E402
from repro.core import distance as distance_mod  # noqa: E402
from repro.core import vamana  # noqa: E402
from repro.core.quant import RabitQuantizer  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402

CACHE_DIR = os.path.join(os.path.dirname(__file__), ".cache")
OUT_DIR = os.path.join(os.path.dirname(__file__), "out")


def set_backend(name: str) -> None:
    """Select the DistanceEngine backend for every system the benchmarks
    build (threads run.py's --backend flag through SystemConfig's default)."""
    distance_mod.set_default_backend(name)


def active_backend() -> str:
    """The engine name systems will actually get ('default' resolved), so
    results.json records reality."""
    return distance_mod.resolved_backend()


def set_fuse(on: bool, rows: int | None = None,
             shared: bool | None = None, overlap: bool | None = None) -> None:
    """Enable cross-query fused score dispatch for every system the
    benchmarks build (threads run.py's --fuse / --shared-rendezvous /
    --overlap-flush flags through SystemConfig)."""
    baselines_mod.set_default_fuse(on, rows, shared, overlap)


def fuse_active() -> dict:
    """The fuse settings systems will actually get, for results.json."""
    on, rows = baselines_mod.default_fuse()
    return {"enabled": on, "rows": rows,
            "shared_rendezvous": baselines_mod.default_shared_rendezvous(),
            "overlap_flush": baselines_mod.default_overlap_flush()}


def set_hbm(on: bool, slots: int | None = None) -> None:
    """Enable the device-resident HBM record-cache tier for every
    record-pool system the benchmarks build (threads run.py's --hbm-tier /
    --hbm-slots flags through SystemConfig)."""
    baselines_mod.set_default_hbm(on, slots)


def hbm_active() -> dict:
    """The HBM-tier settings systems will actually get, for results.json."""
    on, slots = baselines_mod.default_hbm()
    return {"enabled": on, "slots": slots}


def set_calibration(path: str) -> None:
    """Load calibrate.py's per-backend CostModel overrides and make every
    system the benchmarks build inherit them (run.py's --calibration flag)."""
    baselines_mod.set_default_calibration(baselines_mod.load_calibration(path))


def set_device_beam(on: bool) -> None:
    """Enable the fused on-device beam step for every system the benchmarks
    build (threads run.py's --device-beam flag through SystemConfig)."""
    baselines_mod.set_default_device_beam(on)


def device_beam_active() -> bool:
    """The device-beam setting systems will actually get, for results.json."""
    return baselines_mod.default_device_beam()


def set_scheduler(scheduler: str, sla_ms: float | None = None) -> None:
    """Select the engine's scheduling policy (and optional per-query SLA in
    milliseconds) for every system the benchmarks build (threads run.py's
    --scheduler / --sla-ms flags through SystemConfig)."""
    baselines_mod.set_default_scheduler(scheduler, sla_ms)


def scheduler_active() -> dict:
    """The scheduler settings systems will actually get, for results.json."""
    scheduler, sla_ms = baselines_mod.default_scheduler()
    return {"policy": scheduler, "sla_ms": sla_ms}


def pallas_mode() -> bool | None:
    """Whether the pallas backend runs its kernels in interpret mode (True)
    or compiled (False); None when the active backend isn't pallas.
    Recorded in results.json so runs on real accelerators are
    distinguishable from CPU interpret-mode runs."""
    if active_backend() != "pallas":
        return None
    return interpret_mode()


def best_of(fn, reps: int = 5) -> float:
    """Min wall-clock of ``fn()`` over ``reps`` runs (micro-timing floor)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def compiled_vs_interpret(make_fn, reps: int = 5,
                          mode: bool | None = None) -> dict:
    """Time one device operation in compiled and pallas-interpret modes.

    ``make_fn(interpret: bool)`` returns a zero-arg callable executing ONE
    invocation (it must block on the result); the harness warms each mode
    before timing so trace/compile time never lands in the measurement.
    ``compiled_s`` times the engine's NATIVE mode — ``pallas_interpret``
    records which mode that actually was, so results.json from a CPU
    interpret-mode run is distinguishable from a real accelerator run.
    ``interpret_s`` is measured only when the engine compiled for real (an
    interpret-mode process has no faster mode to compare against — and
    force-compiling its kernels would fail, which is why it interprets).
    ``mode`` overrides the native-mode probe: pass the timed engine's own
    ``interpret`` flag when it isn't the session default backend (the
    module-level ``pallas_mode()`` reflects the DEFAULT engine only)."""
    if mode is None:
        mode = pallas_mode()
    fn = make_fn(bool(mode))  # the engine's NATIVE interpret flag
    fn()  # warm: compile outside the timed region
    rec = {
        "compiled_s": best_of(fn, reps),
        "interpret_s": None,
        "pallas_interpret": mode,
    }
    if mode is False:
        fi = make_fn(True)
        fi()
        rec["interpret_s"] = best_of(fi, reps)
    return rec


class Workload:
    """dataset + graph + quantized base, disk-cached by key."""

    def __init__(self, name, n, d, n_queries, R, L, seed=0, query_skew=1.2):
        self.key = f"{name}-n{n}-d{d}-q{n_queries}-R{R}-L{L}-s{seed}"
        os.makedirs(CACHE_DIR, exist_ok=True)
        path = os.path.join(CACHE_DIR, self.key + ".pkl")
        if os.path.exists(path):
            with open(path, "rb") as f:
                self.ds, self.graph, self.qb = pickle.load(f)
            return
        self.ds = dataset_mod.make_dataset(
            n=n, d=d, n_queries=n_queries, k=10, seed=seed,
            query_skew=query_skew, name=name,
        )
        self.graph = vamana.build_vamana(self.ds.base, R=R, L=L, seed=seed)
        self.qb = RabitQuantizer(d, seed=seed).fit_encode(self.ds.base)
        with open(path, "wb") as f:
            pickle.dump((self.ds, self.graph, self.qb), f)


_WORKLOADS: dict[str, Workload] = {}


def sift_like(quick: bool = True) -> Workload:
    key = f"sift-{quick}"
    if key not in _WORKLOADS:
        if quick:
            _WORKLOADS[key] = Workload("siftq", n=6000, d=64, n_queries=300, R=24, L=48)
        else:
            _WORKLOADS[key] = Workload("sift", n=20000, d=128, n_queries=800, R=32, L=64)
    return _WORKLOADS[key]


def gist_like(quick: bool = True) -> Workload:
    key = f"gist-{quick}"
    if key not in _WORKLOADS:
        if quick:
            _WORKLOADS[key] = Workload("gistq", n=3000, d=480, n_queries=150, R=24, L=48)
        else:
            _WORKLOADS[key] = Workload("gist", n=6000, d=960, n_queries=300, R=32, L=64)
    return _WORKLOADS[key]


def result_ids(results, k: int = 10) -> np.ndarray:
    """Stack per-query QueryResult ids into an (n, k) matrix for recall_at_k,
    -1-padded when a query returned fewer than k neighbors."""
    out = np.full((len(results), k), -1, dtype=np.int64)
    for i, r in enumerate(results):
        m = min(k, len(r.ids))
        out[i, :m] = r.ids[:m]
    return out


def fmt_table(headers: list[str], rows: list[list]) -> str:
    widths = [
        max(len(str(h)), max((len(str(r[i])) for r in rows), default=0))
        for i, h in enumerate(headers)
    ]
    def line(vals):
        return "  ".join(str(v).ljust(w) for v, w in zip(vals, widths))
    sep = "  ".join("-" * w for w in widths)
    return "\n".join([line(headers), sep] + [line(r) for r in rows])
