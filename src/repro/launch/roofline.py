"""Roofline analysis over the dry-run manifests (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh) cell, all in seconds per step:

  compute    = HLO_dot_FLOPs_per_device / peak bf16 FLOP/s
  memory     = HLO_bytes_per_device     / peak HBM bytes/s
  collective = collective_bytes_per_device / ICI bytes/s per link

with the peaks of the cell's chip from ``PEAKS``, keyed by JAX's
``device_kind``.  The dry-run meshes are TPU v5e pods.

FLOPs and collective bytes are trip-count-corrected (hlo_analysis).  The
memory term uses fusion-boundary traffic of the CPU-backend HLO — an UPPER
bound on TPU HBM traffic (a TPU backend fuses more, and Pallas kernels keep
attention working sets in VMEM), flagged as such in the report.  The
roofline fraction reported for compute-dominated cells is
compute / max(terms); for bound cells the dominant term itself is the
optimization target of §Perf.

  python -m repro.launch.roofline [--markdown] [--multi-pod]
"""

from __future__ import annotations

import argparse
import glob
import json
import os

# Published per-chip peaks, keyed by ``jax.devices()[i].device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip
# interconnect (200 GB/s over 4 links, 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16 * 2**30,
        "hbm_bw": 819e9, "ici_link_bw": 50e9,
    },
}

DRYRUN_DEVICE_KIND = "TPU v5 lite"  # the chip the dry-run meshes describe


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]


OUT_DIR = os.path.join(os.path.dirname(__file__), "out", "dryrun")


def model_flops_per_device(rec: dict) -> float:
    """6*N_active*D (train) or 2*N_active*D (inference fwd), per device."""
    m = rec.get("model")
    if not m:
        return 0.0
    n_act = m["active_params"]
    kind = rec.get("kind", "train")
    B = rec.get("global_batch", 0)
    S = rec.get("seq_len", 0)
    ndev = rec["n_devices"]
    if kind == "train":
        return 6.0 * n_act * B * S / ndev
    if kind == "prefill":
        return 2.0 * n_act * B * S / ndev
    if kind == "decode":
        return 2.0 * n_act * B / ndev
    return 0.0


def load_cells(multi_pod: bool | None = None) -> list[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(OUT_DIR, "*.json"))):
        rec = json.load(open(path))
        if multi_pod is not None and rec.get("multi_pod") != multi_pod:
            continue
        cells.append(rec)
    return cells


def analyze(rec: dict) -> dict:
    if rec["status"] != "ok":
        return {"arch": rec["arch"], "shape": rec["shape"],
                "multi_pod": rec["multi_pod"], "status": rec["status"],
                "reason": rec.get("reason", rec.get("error", ""))[:90]}
    c = rec["cost"]
    coll = rec["collectives"]
    pk = peaks(rec.get("device_kind", DRYRUN_DEVICE_KIND))
    t_compute = c["flops_per_device"] / pk["bf16_flops"]
    t_memory = c["bytes_accessed_per_device"] / pk["hbm_bw"]
    t_coll = coll["collective_bytes_per_device"] / pk["ici_link_bw"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec)
    useful = mf / max(c["flops_per_device"], 1e-9)
    frac = t_compute / max(terms[dominant], 1e-12)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "multi_pod": rec["multi_pod"],
        "status": "ok",
        "t_compute_s": t_compute, "t_memory_s": t_memory, "t_collective_s": t_coll,
        "dominant": dominant,
        "roofline_fraction": frac,
        "model_flops_per_device": mf,
        "useful_flops_ratio": useful,
        "mem_per_device_gib": rec["memory"]["peak_estimate_bytes"] / 2**30,
        "fits_hbm_16g": rec["memory"]["peak_estimate_bytes"] < pk["hbm_bytes"],
    }


def bottleneck_note(row: dict) -> str:
    d = row["dominant"]
    if d == "memory":
        return "fuse/rematerialize: cut fusion-boundary traffic (attention mask + scan carries)"
    if d == "collective":
        return "reshard or overlap: reduce per-layer TP reductions / FSDP gathers"
    return "compute-bound: raise MFU via larger per-device tiles"


def markdown_table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | roofline frac | useful FLOP ratio | mem GiB | fits 16G |")
    sep = "|" + "---|" * 11
    out = [hdr, sep]
    for r in rows:
        if r["status"] != "ok":
            out.append(
                f"| {r['arch']} | {r['shape']} | {'2pod' if r['multi_pod'] else '1pod'} | "
                f"— | — | — | skipped | — | — | — | {r.get('reason','')[:60]} |"
            )
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {'2pod' if r['multi_pod'] else '1pod'} | "
            f"{r['t_compute_s']:.3g} | {r['t_memory_s']:.3g} | {r['t_collective_s']:.3g} | "
            f"{r['dominant']} | {r['roofline_fraction']:.2f} | "
            f"{r['useful_flops_ratio']:.2f} | {r['mem_per_device_gib']:.2f} | "
            f"{'yes' if r['fits_hbm_16g'] else 'NO'} |"
        )
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all-meshes", action="store_true")
    args = ap.parse_args()

    mp = None if args.all_meshes else args.multi_pod
    rows = [analyze(r) for r in load_cells(mp)]
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["multi_pod"]))
    if args.markdown:
        print(markdown_table(rows))
    else:
        for r in rows:
            if r["status"] != "ok":
                print(f"{r['arch']:24s} {r['shape']:12s} skipped: {r.get('reason','')[:60]}")
                continue
            print(
                f"{r['arch']:24s} {r['shape']:12s} {'2pod' if r['multi_pod'] else '1pod'} "
                f"C={r['t_compute_s']:.3g}s M={r['t_memory_s']:.3g}s X={r['t_collective_s']:.3g}s "
                f"dom={r['dominant']:10s} frac={r['roofline_fraction']:.2f} "
                f"useful={r['useful_flops_ratio']:.2f} mem={r['mem_per_device_gib']:.1f}GiB"
            )
    path = os.path.join(os.path.dirname(OUT_DIR), "roofline.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"\n-> {path}", flush=True)


if __name__ == "__main__":
    main()
