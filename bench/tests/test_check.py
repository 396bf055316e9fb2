"""The comparison itself, on a small corpus whose answers are known."""

import numpy as np
import pytest

from bench import check, gen, loops

SEED = 2**31 + 77
N, D = 600, 96


def _window(ids, d2, qidx):
    return loops.Window(qidx, ids, d2, 1.0, [1.0])


@pytest.fixture(scope="module")
def data():
    base = gen.make_base(SEED, N, D)
    pool = gen.make_queries(SEED, N, D, 16, 1.2)
    qidx = np.arange(len(pool))
    near = gen.exact_topk(base, pool, check.FAR_RANK)
    return base, pool, qidx, near


def _numbers(data, ids):
    base, pool, qidx, _ = data
    return check.measure(base, pool, _window(ids, gen.exact_dist2(base, pool, ids), qidx))


def test_exact_answers(data):
    num = _numbers(data, data[3][:, :check.K])
    assert num == {"bad_answers": 0, "recall_at_10": 1.0, "far_answer_share": 0.0,
                   "dist_gap_max": 0.0}


def test_near_misses_lower_recall_only(data):
    """Ranks 10..19 miss the top-10 but stay inside the top-FAR_RANK."""
    num = _numbers(data, data[3][:, check.K:2 * check.K])
    assert num["recall_at_10"] == 0.0 and num["far_answer_share"] == 0.0


def test_far_answers(data):
    """Ids beyond the exact top-FAR_RANK of every query count as far."""
    base, pool, qidx, _ = data
    full = gen.exact_topk(base, pool, N)
    num = _numbers(data, full[:, -check.K:])
    assert num["far_answer_share"] == 1.0 and num["recall_at_10"] == 0.0


def test_judge_names_only_the_guaranteed(data):
    num = _numbers(data, data[3][:, :check.K])
    ok, checks = check.judge(num, {"far_answer_share_max": 0.02, "dist_gap_max": 0.1})
    assert ok and list(checks) == ["bad_answers", "far_answer_share", "dist_gap_max"]
    ok, checks = check.judge(dict(num, far_answer_share=0.5), {"far_answer_share_max": 0.02})
    assert not ok and not checks["far_answer_share"]["ok"]
