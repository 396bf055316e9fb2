"""Backend-aware cost calibration: micro-time per-backend constants.

The simulator charges every batched distance evaluation one amortized
``CostModel.batch_dispatch_s`` and every registered index one
``table_upload_s`` — but a scalar loop, a BLAS ufunc dispatch, and a Pallas
kernel launch (let alone an interpret-mode one) have wildly different real
overheads.  This module measures them:

  * dispatch  — per-call overhead of an id-based level-1 estimate, extracted
    by timing a 1-row call against a large call and subtracting the per-row
    slope (classic y = a + b*m fit at two points, min-of-reps);
  * full dispatch — the same two-point fit over the exact fp32 path
    (``refine_full``, the BLAS GEMV the DiskANN-style systems refine with):
    a ufunc/GEMV launch is not priced like the int4 table kernel, so
    ``CostModel.full_dispatch_s`` is calibrated apart from
    ``batch_dispatch_s``;
  * row cost  — the slope itself (diagnostic: it should track the CostModel
    per-dim constants);
  * upload    — wall-clock of ``register_index`` on a fresh engine (the
    register-once table pin; device_put for pallas, view construction for
    the host backends).

Results are written to ``benchmarks/out/calibration.json`` as
``{backend: {cost_field: seconds, ...}}`` — exactly the override format
``SystemConfig.calibration`` (or ``baselines.set_default_calibration``, the
hook behind ``run.py --calibration``) consumes, so simulated seconds track
the measured wall-clock ratios recorded in ``benchmarks/out/results.json``.

  python -m benchmarks.calibrate [--quick | --full] [--backends a,b,...]
"""

from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks import common

import numpy as np  # noqa: E402

from repro.core import beam as beam_mod  # noqa: E402
from repro.core import distance as distance_mod  # noqa: E402
from repro.core.quant import RabitQuantizer  # noqa: E402

# CostModel fields the emitted overrides may set; everything else in the
# record is diagnostic and ignored by baselines.apply_calibration.
COST_FIELDS = (
    "batch_dispatch_s", "full_dispatch_s", "table_upload_s", "beam_step_s",
)


def _beam_req(qb, pq, state, ids):
    """A minimal level-1 BeamRequest for micro-timing (flop_s is cost-model
    input only — the engine never reads it)."""
    return beam_mod.BeamRequest(
        kind="estimate", state=state, fresh=np.asarray(ids, np.int64),
        explored=np.zeros(0, np.int64),
        insert_ids=np.zeros(0, np.int64),
        insert_ds=np.zeros(0, np.float32),
        rows=int(np.asarray(ids).size), flop_s=0.0, pq=pq, qb=qb,
    )


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate_backend(
    name: str, n: int = 8192, d: int = 64, big: int = 2048, reps: int = 5,
    seed: int = 0,
) -> dict:
    """Measured constants for one backend over a synthetic (n, d) index."""
    rng = np.random.default_rng(seed)
    base = rng.standard_normal((n, d)).astype(np.float32)
    qb = RabitQuantizer(d, seed=seed).fit_encode(base)
    pq = RabitQuantizer.prepare_query(qb, rng.standard_normal(d).astype(np.float32))
    ids_small = rng.integers(0, n, 1).astype(np.int64)
    ids_big = rng.integers(0, n, big).astype(np.int64)

    eng = distance_mod.get_engine(name)
    resolved = eng.name
    # warm up: registers the table and compiles/jits the kernel wrappers so
    # the timed calls see the steady-state dispatch cost, not compile time
    eng.estimate(qb, pq, ids_small)
    eng.estimate(qb, pq, ids_big)
    eng.refine_ids(qb, pq, ids_big)

    t_small = _best_of(lambda: eng.estimate(qb, pq, ids_small), reps)
    t_big = _best_of(lambda: eng.estimate(qb, pq, ids_big), reps)
    row_s = max(t_big - t_small, 0.0) / max(big - 1, 1)
    dispatch_s = max(t_small - row_s, 1e-9)

    # same two-point fit over the exact fp32 path: refine_full is a dense
    # GEMV over a materialized vector matrix, dispatched differently from
    # the int4 table kernels (BLAS vs kernel launch)
    q = rng.standard_normal(d).astype(np.float32)
    vec_small = base[ids_small]
    vec_big = base[ids_big]
    eng.refine_full(q, vec_small)
    eng.refine_full(q, vec_big)
    tf_small = _best_of(lambda: eng.refine_full(q, vec_small), reps)
    tf_big = _best_of(lambda: eng.refine_full(q, vec_big), reps)
    full_row_s = max(tf_big - tf_small, 0.0) / max(big - 1, 1)
    full_dispatch_s = max(tf_small - full_row_s, 1e-9)

    # fused beam step: the same two-point fit over beam_step_many — the
    # single launch that scores, masks, merges, and selects the frontier.
    # The states/requests are prebuilt OUTSIDE the timed region (repeat
    # steps re-score the same rows against an already-visited mask: the
    # kernel work per row is identical, which is all the fit needs).
    st_small = eng.beam_new(64, n)
    st_big = eng.beam_new(64, n)
    rq_small = _beam_req(qb, pq, st_small, ids_small)
    rq_big = _beam_req(qb, pq, st_big, ids_big)
    eng.beam_step_many(qb, [rq_small])
    eng.beam_step_many(qb, [rq_big])
    tb_small = _best_of(lambda: eng.beam_step_many(qb, [rq_small]), reps)
    tb_big = _best_of(lambda: eng.beam_step_many(qb, [rq_big]), reps)
    beam_row_s = max(tb_big - tb_small, 0.0) / max(big - 1, 1)
    beam_step_s = max(tb_small - beam_row_s, 1e-9)

    # time ONLY register_index (the table pin), not engine construction:
    # registration is idempotent per engine, so each rep needs a fresh engine
    # — built outside the timed region
    upload_s = float("inf")
    for e in [distance_mod.get_engine(name) for _ in range(reps)]:
        t0 = time.perf_counter()
        e.register_index(qb)
        upload_s = min(upload_s, time.perf_counter() - t0)
    upload_s = max(upload_s, 1e-9)

    rec = {
        "backend": resolved,
        "batch_dispatch_s": dispatch_s,
        "full_dispatch_s": full_dispatch_s,
        "table_upload_s": upload_s,
        "beam_step_s": beam_step_s,
        "estimate_row_s": row_s,
        "full_row_s": full_row_s,
        "beam_row_s": beam_row_s,
        "n": n,
        "d": d,
        "big": big,
    }
    if resolved == "pallas":
        rec["pallas_interpret"] = bool(eng.interpret)
    return rec


def run(quick: bool = True, backends: list[str] | None = None) -> dict:
    if backends is None:
        backends = ["scalar", "batch", "pallas"]
    n, big, reps = (4096, 1024, 3) if quick else (16384, 4096, 7)

    records = {}
    for name in backends:
        records[name] = calibrate_backend(name, n=n, big=big, reps=reps)

    rows = [
        [name, rec["backend"], f"{rec['batch_dispatch_s'] * 1e6:.2f}",
         f"{rec['full_dispatch_s'] * 1e6:.2f}",
         f"{rec['beam_step_s'] * 1e6:.2f}",
         f"{rec['estimate_row_s'] * 1e9:.1f}",
         f"{rec['table_upload_s'] * 1e6:.1f}"]
        for name, rec in records.items()
    ]
    text = common.fmt_table(
        ["backend", "resolved", "dispatch us", "full us", "beam us",
         "row ns", "upload us"], rows
    )

    # sanity: the ordering argument of the paper — a kernel-launch dispatch
    # costs more than a ufunc dispatch, and pinning tables on the device
    # (device_put) costs more than aliasing host views — the one-time price
    # register-once pays so the per-hop path never re-uploads
    checks = {
        "dispatch_positive": all(
            r["batch_dispatch_s"] > 0 for r in records.values()
        ),
        "upload_positive": all(
            r["table_upload_s"] > 0 for r in records.values()
        ),
        "full_dispatch_positive": all(
            r["full_dispatch_s"] > 0 for r in records.values()
        ),
        "beam_step_positive": all(
            r["beam_step_s"] > 0 for r in records.values()
        ),
    }
    if "pallas" in records and records["pallas"]["backend"] == "pallas":
        checks["pallas_dispatch_heavier_than_batch"] = (
            records["pallas"]["batch_dispatch_s"]
            > records["batch"]["batch_dispatch_s"]
        )
        checks["pallas_upload_heavier_than_host_view"] = (
            records["pallas"]["table_upload_s"]
            > records["batch"]["table_upload_s"]
        )

    out = {"name": "calibration", "records": records, "text": text,
           "checks": checks}
    os.makedirs(common.OUT_DIR, exist_ok=True)
    path = os.path.join(common.OUT_DIR, "calibration.json")
    with open(path, "w") as f:
        json.dump(records, f, indent=1, default=float)
    out["path"] = path
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small index, few reps (the default)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--backends", default=None,
                    help="comma-separated subset (default: all available)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 if any sanity check fails")
    args = ap.parse_args()
    backends = args.backends.split(",") if args.backends else None
    res = run(quick=not args.full, backends=backends)
    print(res["text"])
    ok = True
    for check, passed in res["checks"].items():
        ok &= bool(passed)
        print(f"  [{'PASS' if passed else 'FAIL'}] {check}")
    print(f"overrides -> {res['path']}")
    if args.strict and not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
