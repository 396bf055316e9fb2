#!/usr/bin/env python3
"""The control for ``correct``: the reference put in the program's place at
the next precision below the configuration's, and planted faults.

The configuration's level-2 codes are int4, so the control is the plain
reference, exact brute force, run over corpus vectors quantized per vector
to int2 (uniform, min/max range, as the program's level-2 scheme but with 3
steps instead of 15), and reporting int2 distances.  It must come out not
correct; its readings set the upper end of each limit it fails
(``dist_gap_max``, and ``far_answer_share`` where the configuration judges
it).

  python bench/control.py --workload gist960-flat.closed-b256 --seeds 1,2,3 --seconds 5

prints one result line per seed (the numbers of the checks the
configuration judges).
``--program`` runs the program itself instead, on the same seeds, in the
same process, for the lower readings.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

BITS = 2


class QuantScan:
    """Brute-force top-k over ``bits``-bit per-vector quantized vectors, on
    the default device; the distances it states are the quantized ones."""

    def __init__(self, base: np.ndarray, k: int = 10, bits: int = BITS):
        import jax

        levels = (1 << bits) - 1
        xs = []
        for s in range(0, len(base), 65536):
            x = base[s:s + 65536]
            lo = x.min(axis=1, keepdims=True)
            step = np.maximum(x.max(axis=1, keepdims=True) - lo, 1e-12) / levels
            xs.append((np.rint((x - lo) / step) * step + lo).astype(np.float32))
        self.xq = jax.device_put(np.concatenate(xs))
        self.k = k

        @jax.jit
        def top(q, xq):
            with jax.default_matmul_precision("highest"):
                d2 = ((q * q).sum(1)[:, None] - 2.0 * (q @ xq.T)
                      + (xq * xq).sum(1)[None, :])
                _, ids = jax.lax.top_k(-d2, k)
                diff = xq[ids] - q[:, None, :]
                return ids, (diff * diff).sum(-1)

        self._top = top

    def search(self, q: np.ndarray):
        ids, d2 = self._top(q, self.xq)
        return np.asarray(ids), np.asarray(d2)


class ControlState:
    """A runner state with the control in the program's place."""

    def __init__(self, config: dict, seed: int):
        from bench import gen

        self._base = gen.make_base(seed, config["n"], config["d"])
        self.search = QuantScan(self._base, k=config["search"]["k"]).search

    def reset_counters(self):
        pass

    def counters(self) -> dict:
        return {}

    def free(self):
        pass

    def base(self) -> np.ndarray:
        return self._base


def main(argv=None) -> int:
    import argparse

    bench_dir = os.path.dirname(os.path.abspath(__file__))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(bench_dir, ".cache", "jax")
    sys.path[:0] = [os.path.dirname(bench_dir), os.path.join(os.path.dirname(bench_dir), "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--program", action="store_true",
                    help="run the program, not the control")
    args = ap.parse_args(argv)
    setup = None if args.program else ControlState
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False, setup=setup,
                               log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps({"seed": seed, "who": "program" if args.program else "control",
                          "correct": res["correct"], "attempted": res["attempted"],
                          "checks": {k: v["value"] for k, v in res["checks"].items()},
                          "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
