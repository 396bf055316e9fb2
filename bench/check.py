"""The comparison that decides ``correct``.

The plain reference is exact brute force in float32 (``gen.exact_topk``) over
the corpus regenerated from the seed; it imports nothing of the program and
takes nothing the program made.  Every answer of the window is judged:

- ``bad_answers``: answers without k distinct ids inside the corpus, or with a
  distance that is not finite (a request the program dropped is one);
- ``recall_at_10``: recall@10 of all answers against the exact top-10;
- ``far_answer_share``: the share of all answer ids that lie outside the
  query's exact top-``FAR_RANK`` (five times k).  A search that ranks by
  its int4 codes misses the top-10 only by near neighbours; coarser
  distances, a walk that stops early or answers meant for another query
  reach far outside it.  On a small window it separates a sound run from
  the control where recall, which near misses move, does not;
- ``dist_gap_max``: the widest relative gap between the squared distance an
  answer states for an id and that id's exact squared distance.  The
  configuration's codes (1-bit level 1, int4 level 2) bound it; an id altered
  after its distance was computed, or distances from coarser codes, do not.

The limits are the configuration's ``guarantees``: a number is judged
where they name its limit, ``bad_answers`` always.
"""

from __future__ import annotations

import numpy as np

from bench import gen

K = 10
FAR_RANK = 5 * K

# compared number -> (op, the key of its limit in the configuration's guarantees)
LIMITS = {
    "bad_answers": ("<=", None),
    "recall_at_10": (">=", "recall_at_10_min"),
    "far_answer_share": ("<=", "far_answer_share_max"),
    "dist_gap_max": ("<=", "dist_gap_max"),
}


def _pair_dist2(base, pool, qidx, ids):
    """Exact squared distances for (query row, corpus id) pairs, each unique
    pair computed once."""
    n = base.shape[0]
    key = qidx.astype(np.int64) * n + ids
    uniq, inv = np.unique(key, return_inverse=True)
    out = np.empty(len(uniq))
    for s in range(0, len(uniq), 4096):
        u = uniq[s:s + 4096]
        out[s:s + 4096] = gen.exact_dist2(base, pool[u // n], (u % n)[:, None])[:, 0]
    return out[inv]


def recall_rows(ids: np.ndarray, truth: np.ndarray) -> np.ndarray:
    """Hits per row: ids of ``truth``'s row found among the answer's ids
    (each true id counted once, so repeated ids never inflate it)."""
    hits = np.empty(len(ids), np.int64)
    for s in range(0, len(ids), 65536):
        a, t = ids[s:s + 65536], truth[s:s + 65536]
        hits[s:s + 65536] = (t[:, :, None] == a[:, None, :]).any(axis=2).sum(axis=1)
    return hits


def measure(base: np.ndarray, pool: np.ndarray, win) -> dict:
    """The compared numbers for one window."""
    n = base.shape[0]
    ids, d2, qidx = win.ids, win.dist2, win.pool_idx
    in_range = (ids >= 0) & (ids < n)
    srt = np.sort(ids, axis=1)
    distinct = (srt[:, 1:] != srt[:, :-1]).all(axis=1)
    finite = np.isfinite(d2).all(axis=1)
    good = in_range.all(axis=1) & distinct & finite
    rows, inv = np.unique(qidx, return_inverse=True)
    near = gen.exact_topk(base, pool[rows], min(FAR_RANK, n))[inv]
    hits = recall_rows(ids, near[:, :K])
    far = K - recall_rows(ids, near)
    gap = 0.0
    if good.any():
        gi, gd, gq = ids[good], d2[good], qidx[good]
        exact = _pair_dist2(base, pool, np.repeat(gq, K), gi.reshape(-1)).reshape(gi.shape)
        gap = float(np.max(np.abs(gd - exact) / np.maximum(exact, 1e-12)))
    return {
        "bad_answers": int((~good).sum()),
        "recall_at_10": float(hits.sum() / (K * max(1, len(ids)))),
        "far_answer_share": float(far.sum() / (K * max(1, len(ids)))),
        "dist_gap_max": gap,
    }


def judge(numbers: dict, guarantees: dict) -> tuple[bool, dict]:
    """(correct, checks): each judged number beside its limit."""
    checks, ok = {}, True
    for name, (op, key) in LIMITS.items():
        if key is not None and key not in guarantees:
            continue
        lim = 0 if key is None else guarantees[key]
        v = numbers[name]
        good = v >= lim if op == ">=" else v <= lim
        ok &= bool(good)
        checks[name] = {"value": v, "op": op, "limit": lim, "ok": bool(good)}
    return ok, checks
