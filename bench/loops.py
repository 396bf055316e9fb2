"""Traffic: the two loops a cell can ask for, driven only by the cell's file.

A cell's ``loop`` names its kind and parameters:

- ``closed``: back-to-back calls of ``batch`` queries, the next call once the
  last has returned.  ``replace`` says whether queries are drawn from the
  pool with replacement; without it the pool is walked in a seeded order and
  the window ends early when the pool runs out.  ``warmup_calls`` calls on
  queries kept out of the window come first, or, with ``"warmup": "replay"``,
  the window's own calls on a throwaway copy of the system (see
  ``closed_replay``).
- ``open``: Poisson arrivals at ``rate_qps`` from the seed over the window; a
  micro-batcher takes every request that is due, up to ``max_batch``, and
  pads the call to the smallest of ``buckets`` that holds it.  Every bucket
  is called ``warmup_calls`` times in set-up.

``search(q)`` takes a (B, d) float32 batch and returns ``(ids, dist2)``
(B, k) arrays on the host.  Both loops return a ``Window``.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import gen
from bench.spans import span


@dataclasses.dataclass
class Window:
    pool_idx: np.ndarray       # (N,) pool row each request asked for
    ids: np.ndarray            # (N, k) returned ids, -1 where none
    dist2: np.ndarray          # (N, k) returned squared distances
    seconds: float             # window wall time
    call_s: list               # host-clock time of each call
    latency_s: np.ndarray | None = None     # open loop: done - due
    queue_wait_s: np.ndarray | None = None  # open loop: call start - due
    late_s: float = 0.0        # open loop: how far the last call started past
                               # the schedule's end


def _pad(q: np.ndarray, size: int) -> np.ndarray:
    if q.shape[0] == size:
        return q
    return np.concatenate([q, np.repeat(q[:1], size - q.shape[0], axis=0)])


def _store(ids, d2, k):
    out_i = np.full((len(ids), k), -1, np.int64)
    out_d = np.full((len(ids), k), np.nan, np.float64)
    m = min(k, ids.shape[1])
    out_i[:, :m] = ids[:, :m]
    out_d[:, :m] = d2[:, :m]
    return out_i, out_d


# ------------------------------------------------------------------ closed


def _closed_plan(loop: dict, pool_size: int, seed: int):
    rng = gen.rng_for(seed, 3)
    b = loop["batch"]
    warm = 0 if loop.get("warmup") == "replay" else loop.get("warmup_calls", 1)
    if loop.get("replace", True):
        warm_batches = [rng.integers(0, pool_size, b) for _ in range(warm)]
        return warm_batches, (rng.integers(0, pool_size, b) for _ in iter(int, 1))
    order = rng.permutation(pool_size)
    calls = pool_size // b
    batches = [order[i * b:(i + 1) * b] for i in range(calls)]
    return batches[calls - warm:], iter(batches[:calls - warm])


def closed_warm(search, pool, loop, seed):
    warm, _ = _closed_plan(loop, len(pool), seed)
    for idx in warm:
        search(pool[idx])


def closed_replay(search, pool, loop, seed, seconds):
    """The window's own calls, for at least ``seconds`` and one call more.

    A system whose compiled shapes depend on the data (the served engine
    fuses however many requests meet in a flush) cannot be warmed by other
    queries.  Its simulation is deterministic, so the same calls on a fresh
    copy of the system meet the same shapes: run them on a copy that is
    then thrown away, and the window compiles nothing."""
    closed_window(search, pool, loop, seed, seconds, extra_calls=1)


def closed_window(search, pool, loop, seed, seconds, k=10, extra_calls=0) -> Window:
    _, batches = _closed_plan(loop, len(pool), seed)
    idx_all, ids_all, d2_all, call_s = [], [], [], []
    t0 = time.perf_counter()
    t_end = t0
    for idx in batches:
        c0 = time.perf_counter()
        ids, d2 = search(pool[idx])
        t_end = time.perf_counter()
        call_s.append(t_end - c0)
        i, d = _store(np.asarray(ids), np.asarray(d2), k)
        idx_all.append(idx)
        ids_all.append(i)
        d2_all.append(d)
        if t_end - t0 >= seconds:
            if extra_calls == 0:
                break
            extra_calls -= 1
    ids = np.concatenate(ids_all)
    return Window(np.concatenate(idx_all), ids, np.concatenate(d2_all),
                  t_end - t0, call_s)


# -------------------------------------------------------------------- open


def _bucket(buckets, m):
    for b in buckets:
        if b >= m:
            return b
    raise ValueError(f"{m} requests exceed the largest bucket {buckets[-1]}")


def open_warm(search, pool, loop, seed):
    for b in loop["buckets"]:
        for _ in range(loop.get("warmup_calls", 2)):
            search(_pad(pool[:min(b, len(pool))], b))


def open_schedule(loop, pool_size, seed, seconds):
    """Due times (s from the window's start) and pool rows of every request."""
    rng = gen.rng_for(seed, 4)
    rate = float(loop["rate_qps"])
    n = int(rate * seconds * 1.2 + 64)
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    due = due[due < seconds]
    return due, rng.integers(0, pool_size, len(due))


def open_window(search, pool, loop, seed, seconds, k=10) -> Window:
    due, idx = open_schedule(loop, len(pool), seed, seconds)
    n = len(due)
    buckets, cap = sorted(loop["buckets"]), int(loop["max_batch"])
    ids = np.full((n, k), -1, np.int64)
    d2 = np.full((n, k), np.nan, np.float64)
    done = np.full(n, np.nan)
    start = np.full(n, np.nan)
    call_s = []
    i = 0
    t0 = time.perf_counter() + 0.001
    while i < n:
        now = time.perf_counter() - t0
        if due[i] > now:
            with span("bench.wait_arrival"):
                while due[i] > now:
                    if due[i] - now > 0.002:
                        time.sleep(due[i] - now - 0.001)
                    now = time.perf_counter() - t0
        j = min(int(np.searchsorted(due, now, side="right")), i + cap)
        m = j - i
        c0 = time.perf_counter()
        a, b = search(_pad(pool[idx[i:j]], _bucket(buckets, m)))
        c1 = time.perf_counter()
        call_s.append(c1 - c0)
        ids[i:j], d2[i:j] = _store(np.asarray(a)[:m], np.asarray(b)[:m], k)
        start[i:j] = c0 - t0
        done[i:j] = c1 - t0
        i = j
    late = max(0.0, float(start[-1] - seconds)) if n else 0.0
    return Window(idx, ids, d2, float(done[-1]) if n else 0.0, call_s,
                  done - due, start - due, late)


LOOPS = {
    "closed": (closed_warm, closed_window),
    "open": (open_warm, open_window),
}
