"""``correct`` comes out false for the control and for each planted fault.

Each test drives a whole run at a tiny size on the CPU (the harness's look
for a chip skipped) with the timed path broken underneath, and checks which
compared number catches it.  The faults a one-chip cell can have:

- a step that returns its state unchanged: the served engine's fused beam
  step, and the scan's running top-C carry;
- half of the batch left out, its answers copied from the rest;
- one answer altered where it is produced.

(No cell spans chips, so there is no exchange between chips to leave out.)
"""


import jax
import numpy as np
import pytest

from bench import control, harness
from bench.tests.conftest import TINY

SEED = 2**31 + 4242
SERVED = "deep96-graph.zipf-closed"
SCANS = ["gist960-flat.closed-b256", "gist960-flat.open"]


def _run(cell, **kw):
    return harness.run_cell(cell, SEED, 1.0, False, allow_cpu=True,
                            overrides=TINY[cell], log=lambda m: None, **kw)


def _failed(res):
    assert res["correct"] is False
    return {k for k, c in res["checks"].items() if not c["ok"]}


def _n(cell):
    return TINY[cell]["config"]["n"]


@pytest.mark.parametrize("cell", [SERVED] + SCANS)
def test_control_is_not_correct(cell):
    """The reference at int2 in the program's place states distances that
    the configuration's int4 codes would not."""
    assert "dist_gap_max" in _failed(_run(cell, setup=control.ControlState))


# ------------------------------------------------------------------ served


def test_served_stale_beam_step(monkeypatch):
    from repro.core import beam, distance

    def stale(self, qb, reqs):
        return [beam.BeamResult(frontier=np.empty(0, np.int64), window_len=0,
                                tail=float("inf")) for _ in reqs]

    monkeypatch.setattr(distance.DistanceEngine, "beam_step_many", stale)
    assert _failed(_run(SERVED)) & {"far_answer_share", "bad_answers"}


def test_served_half_batch(monkeypatch):
    from repro.core import baselines

    real = baselines.System.run

    def half(self, queries, *a, **kw):
        h = len(queries) // 2
        results, stats = real(self, queries[:h], *a, **kw)
        return results + results[:len(queries) - h], stats

    monkeypatch.setattr(baselines.System, "run", half)
    assert _failed(_run(SERVED))


def test_served_answer_altered(monkeypatch):
    from repro.core import search

    real, n, made = search._finish, _n(SERVED), []

    def altered(refined, k):  # one answer in 16, in warm-up and window alike
        ids, ds = real(refined, k)
        if len(made) % 16 == 0:
            ids = (ids + n // 2) % n
        made.append(1)
        return ids, ds

    monkeypatch.setattr(search, "_finish", altered)
    assert "dist_gap_max" in _failed(_run(SERVED))


# -------------------------------------------------------------------- scan


@pytest.mark.parametrize("cell", SCANS)
def test_scan_stale_carry(cell, monkeypatch):
    def stale_scan(body, init, xs=None, *a, **kw):
        return init, None

    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "scan", stale_scan)
    try:
        assert _failed(_run(cell)) & {"recall_at_10", "bad_answers"}
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def _wrap_scan(monkeypatch, fn):
    from repro.velo import scan_search as mod

    real = mod.scan_search
    monkeypatch.setattr(mod, "scan_search", lambda index, q, **kw: fn(real, index, q, **kw))


@pytest.mark.parametrize("cell", SCANS)
def test_scan_half_batch(cell, monkeypatch):
    def half(real, index, q, **kw):
        h = q.shape[0] // 2
        ids, d2 = (np.asarray(a) for a in real(index, q[:h], **kw))
        rest = q.shape[0] - h
        return np.concatenate([ids, ids[:rest]]), np.concatenate([d2, d2[:rest]])

    _wrap_scan(monkeypatch, half)
    assert _failed(_run(cell))


@pytest.mark.parametrize("cell", SCANS)
def test_scan_answer_altered(cell, monkeypatch):
    n = _n(cell)

    def altered(real, index, q, **kw):
        ids, d2 = (np.array(a) for a in real(index, q, **kw))
        ids[0] = (ids[0] + n // 2) % n
        return ids, d2

    _wrap_scan(monkeypatch, altered)
    assert "dist_gap_max" in _failed(_run(cell))
