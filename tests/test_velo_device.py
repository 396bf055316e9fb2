"""Device plane: batched beam search + scan search vs host plane / ground truth."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.dataset import recall_at_k
from repro.velo import batch_search as bs
from repro.velo import scan_search as ss
from repro.velo.device_cache import (
    DeviceRecordCache,
    FREE,
    LOCKED,
    MARKED,
    OCCUPIED,
)
from repro.core.quant import RabitQuantizer, unpack_nibbles
from repro.velo.index import ext_row_words, from_host, host_arrays, unpack_ext_rows


@pytest.fixture(scope="module")
def dev_index(small_qb, small_graph):
    return from_host(small_qb, small_graph)


def test_batch_search_recall(small_ds, dev_index):
    q = jnp.asarray(small_ds.queries)
    ids, d2, steps = bs.batch_search(dev_index, q, L=48, k=10, max_steps=96)
    rec = recall_at_k(np.asarray(ids), small_ds.groundtruth, 10)
    assert rec > 0.6, f"device graph search recall {rec}"
    assert bool((np.asarray(steps) > 3).all())
    assert np.isfinite(np.asarray(d2)).all()


def test_batch_search_matches_larger_L(small_ds, dev_index):
    """More beam budget must never hurt recall (monotonicity sanity)."""
    q = jnp.asarray(small_ds.queries[:30])
    rs = {}
    for L in (16, 64):
        ids, _, _ = bs.batch_search(dev_index, q, L=L, k=10, max_steps=128)
        rs[L] = recall_at_k(np.asarray(ids), small_ds.groundtruth[:30], 10)
    assert rs[64] >= rs[16]


def test_scan_search_recall(small_ds, dev_index):
    """Two-stage compressed scan is near-exhaustive: recall limited only by
    4-bit refinement noise."""
    q = jnp.asarray(small_ds.queries)
    ids, d2 = ss.scan_search(dev_index, q, k=10, rerank=64)
    rec = recall_at_k(np.asarray(ids), small_ds.groundtruth, 10)
    assert rec > 0.8, f"scan recall {rec}"


def test_scan_beats_graph_recall(small_ds, dev_index):
    """On one shard the exhaustive level-1 scan upper-bounds graph traversal."""
    q = jnp.asarray(small_ds.queries[:40])
    ids_g, _, _ = bs.batch_search(dev_index, q, L=48, k=10, max_steps=96)
    ids_s, _ = ss.scan_search(dev_index, q, k=10, rerank=96)
    rg = recall_at_k(np.asarray(ids_g), small_ds.groundtruth[:40], 10)
    rs_ = recall_at_k(np.asarray(ids_s), small_ds.groundtruth[:40], 10)
    assert rs_ >= rg - 0.02


def test_device_matches_host_distance_semantics(small_ds, small_qb, small_graph, dev_index):
    """Refined distances from the device search equal the host quantizer's."""
    q = jnp.asarray(small_ds.queries[:4])
    ids, d2, _ = bs.batch_search(dev_index, q, L=32, k=5, max_steps=64)
    ids, d2 = np.asarray(ids), np.asarray(d2)
    for i in range(4):
        pq = RabitQuantizer.prepare_query(small_qb, small_ds.queries[i])
        host = RabitQuantizer.refine_dist2(small_qb, pq, ids[i])
        np.testing.assert_allclose(d2[i], host, rtol=2e-3, atol=2e-3)


def test_batch_search_refine_matches_three_tables(small_ds, small_qb, dev_index,
                                                  int4_three_tables):
    """batch_search's distances are the three-table int4 formula's, bit for
    bit, for the ids it returns."""
    q = jnp.asarray(small_ds.queries[:6])
    ids, d2, _ = bs.batch_search(dev_index, q, L=32, k=5, max_steps=64)
    qr = jax.jit(bs._prepare_queries)(dev_index, q)[0]
    want = jax.jit(int4_three_tables(small_qb))(ids, qr)
    np.testing.assert_array_equal(np.asarray(d2).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


# ------------------------------------------------------------- level-2 rows


@pytest.mark.parametrize("d,words", [(64, 16), (96, 16), (960, 128)])
def test_ext_rows_round_trip(d, words):
    """``ext_rows`` holds each row's nibble codes, lo and step, and the
    sentinel's (codes 0, lo 0.0, step 1.0): unpacked on the device they
    equal the host QuantizedBase's, bit for bit; the rest is zeros."""
    rng = np.random.default_rng(d)
    n = 37
    qb = RabitQuantizer(d, seed=1).fit_encode(rng.standard_normal((n, d)).astype(np.float32))
    rows = host_arrays(qb)["ext_rows"]
    assert rows.dtype == np.uint32 and rows.shape == (n + 1, words)
    codes, lo, step = jax.jit(unpack_ext_rows, static_argnums=1)(jnp.asarray(rows), d)
    want_codes = np.concatenate([unpack_nibbles(qb.ext_codes, d), np.zeros((1, d), np.uint8)])
    np.testing.assert_array_equal(np.asarray(codes), want_codes.astype(np.float32))
    for got, want in ((lo, [*qb.ext_lo, 0.0]), (step, [*qb.ext_step, 1.0])):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      np.asarray(want, np.float32).view(np.uint32))
    assert not rows[:, d // 8 + 2:].any()


@pytest.mark.parametrize("d,words", [
    (8, 8), (64, 16), (96, 16), (128, 24), (496, 64),   # up to 64 words: 8s
    (504, 128), (960, 128), (1024, 256),                 # beyond: 128-lane rows
])
def test_ext_row_words_padding_rule(d, words):
    assert ext_row_words(d) == words
    assert words >= d // 8 + 2


def test_ext_rows_refuse_widths_and_8bit_codes():
    with pytest.raises(ValueError):
        ext_row_words(100)
    rng = np.random.default_rng(0)
    qb8 = RabitQuantizer(64, seed=0, ext_bits=8).fit_encode(
        rng.standard_normal((20, 64)).astype(np.float32))
    with pytest.raises(ValueError):
        host_arrays(qb8)


# ------------------------------------------------------------- device cache


def test_device_cache_admit_touch_evict():
    vid_to_page = np.arange(64) // 4
    c = DeviceRecordCache.create(8, vid_to_page, dim=16, R=4)
    vids = np.asarray([1, 2, 3])
    assert not c.resident_mask(vids).any()
    c.admit(
        vids,
        exts=np.zeros((3, 8), np.uint8),
        los=np.zeros(3), steps_=np.ones(3),
        adjs=[np.asarray([4, 5]), np.asarray([6]), np.asarray([7, 8, 9])],
        disk_pages=vid_to_page[vids],
    )
    assert c.resident_mask(vids).all()
    c.touch(vids)
    assert c.hits == 3
    # fill and force eviction
    more = np.arange(10, 20)
    c.admit(more, np.zeros((10, 8), np.uint8), np.zeros(10), np.ones(10),
            [np.asarray([0])] * 10, vid_to_page[more])
    assert (c.slot_state != FREE).sum() == 8
    assert c.evictions > 0
    # evicted records' hybrid pointers must point back at their disk pages
    evicted = [v for v in range(64) if c.record_map[v] < 0]
    for v in evicted:
        assert -(c.record_map[v] + 1) == vid_to_page[v]


def test_device_cache_second_chance():
    vid_to_page = np.arange(16)
    c = DeviceRecordCache.create(2, vid_to_page, dim=8, R=2)
    c.admit(np.asarray([0, 1]), np.zeros((2, 4), np.uint8), np.zeros(2),
            np.ones(2), [np.asarray([1]), np.asarray([0])], vid_to_page[:2])
    c.slot_state[:] = MARKED
    c.touch(np.asarray([0]))          # vid 0 gets its second chance
    slot0 = c.record_map[0]
    assert c.slot_state[slot0] == OCCUPIED
    c.admit(np.asarray([5]), np.zeros((1, 4), np.uint8), np.zeros(1),
            np.ones(1), [np.asarray([0])], vid_to_page[5:6])
    assert c.resident_mask(np.asarray([0]))[0], "hot record must survive"
    assert not c.resident_mask(np.asarray([1]))[0]


def _filled_cache(n_slots=4, n=32):
    vid_to_page = np.arange(n) // 4
    c = DeviceRecordCache.create(n_slots, vid_to_page, dim=16, R=4)
    vids = np.arange(n_slots)
    c.admit(vids, np.full((n_slots, 8), 7, np.uint8), np.zeros(n_slots),
            np.ones(n_slots), [np.asarray([0])] * n_slots, vid_to_page[vids])
    return c, vid_to_page


def test_device_cache_sweep_all_locked():
    """A sweep over a fully-LOCKED cache frees nothing and touches no state:
    LOCKED slots are mid-scatter and must never be reclaimed."""
    c, _ = _filled_cache()
    c.slot_state[:] = LOCKED
    before_map = c.record_map.copy()
    before_vid = c.slot_vid.copy()
    freed = c.sweep(3)
    assert len(freed) == 0
    assert (c.slot_state == LOCKED).all()
    np.testing.assert_array_equal(c.record_map, before_map)
    np.testing.assert_array_equal(c.slot_vid, before_vid)
    assert c.evictions == 0


def test_device_cache_sweep_need_exceeds_slots():
    """`need` far beyond the slot count is capped, not an infinite clock walk;
    an all-OCCUPIED cache yields every slot (demote pass, then evict pass)."""
    c, _ = _filled_cache(n_slots=4)
    freed = c.sweep(100)
    assert len(freed) == 4
    assert (c.slot_state == FREE).all()
    assert c.evictions == 4
    # freed slots' records point back at their disk pages
    for v in range(4):
        assert c.record_map[v] < 0


def test_device_cache_admit_already_resident():
    """Re-admitting a resident vid is a no-op: same slot, payload untouched,
    no second slot consumed."""
    c, vid_to_page = _filled_cache(n_slots=4)
    slot0 = int(c.record_map[0])
    before_ext = c.cache_ext[slot0].copy()
    used_before = int((c.slot_state != FREE).sum())
    c.admit(np.asarray([0]), np.full((1, 8), 99, np.uint8), np.full(1, 5.0),
            np.full(1, 5.0), [np.asarray([1, 2])], vid_to_page[:1])
    assert int(c.record_map[0]) == slot0
    np.testing.assert_array_equal(c.cache_ext[slot0], before_ext)
    assert int((c.slot_state != FREE).sum()) == used_before


def test_hbm_scatter_double_buffer_parity(small_qb):
    """The staged-scatter tier (records parked during step t, installed by
    one batched scatter at the t/t+1 boundary) must land in the SAME state a
    sequential per-record admit reaches, and the device mirror maintained by
    the jitted scatter must stay bit-identical to the host slot arrays."""
    from repro.core.hbm import HbmTier
    from repro.core.store import DecodedRecord

    n = len(small_qb.ext_codes)
    vid_to_page = np.arange(n) // 4

    def record(v):
        return DecodedRecord(
            vid=v, adjacency=np.asarray([(v + 1) % n, (v + 2) % n]),
            ext_payload=small_qb.record_payload(v),
        )

    tier = HbmTier(small_qb, vid_to_page, n_slots=8, R=4)
    ref = DeviceRecordCache.create(
        8, vid_to_page, dim=small_qb.dim, R=4,
        code_cols=small_qb.ext_codes.shape[1],
    )
    tier.device_arrays()  # force the mirror so every scatter updates it
    rng = np.random.default_rng(0)
    for _ in range(6):  # steps, each staging one admit group
        group = rng.choice(n, size=3, replace=False)
        staged = []
        for v in group:
            if tier._stage(int(v), record(int(v))):
                staged.append(int(v))
        assert tier.scatter_staged() == len(staged)
        if staged:  # sequential reference: plain admit of the same group
            recs = [record(v) for v in staged]
            ncode = small_qb.ext_codes.shape[1]
            ref.admit(
                np.asarray(staged),
                np.stack([np.frombuffer(r.ext_payload[:ncode], np.uint8)
                          for r in recs]),
                np.asarray([np.frombuffer(r.ext_payload[ncode:ncode + 4],
                                          np.float32)[0] for r in recs]),
                np.asarray([np.frombuffer(r.ext_payload[ncode + 4:ncode + 8],
                                          np.float32)[0] for r in recs]),
                [r.adjacency.astype(np.int32) for r in recs],
                vid_to_page[staged],
            )
        np.testing.assert_array_equal(tier.cache.record_map, ref.record_map)
        np.testing.assert_array_equal(tier.cache.slot_state, ref.slot_state)
        np.testing.assert_array_equal(tier.cache.slot_vid, ref.slot_vid)
        np.testing.assert_array_equal(tier.cache.cache_ext, ref.cache_ext)
        # the functionally-updated device mirror tracks the host arrays
        ext_d, lo_d, step_d = tier.device_arrays()
        np.testing.assert_array_equal(np.asarray(ext_d), tier.cache.cache_ext)
        np.testing.assert_array_equal(np.asarray(lo_d), tier.cache.cache_lo)
        np.testing.assert_array_equal(np.asarray(step_d),
                                      tier.cache.cache_step)
