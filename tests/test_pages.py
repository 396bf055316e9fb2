"""Slotted page layout invariants (paper §3.3, Fig. 7)."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import pages


records = st.lists(
    st.tuples(
        st.integers(min_value=0, max_value=2**31 - 1),      # vid
        st.integers(min_value=0, max_value=255),            # color
        st.binary(min_size=1, max_size=300),                # payload
    ),
    min_size=1,
    max_size=30,
    unique_by=lambda t: t[0],
)


@given(records)
@settings(max_examples=100, deadline=None)
def test_pack_lookup_roundtrip(entries):
    b = pages.PageBuilder()
    added = []
    for vid, color, payload in entries:
        if b.add(vid, color, payload):
            added.append((vid, color, payload))
    page = b.finalize()
    assert len(page) == pages.PAGE_SIZE
    assert pages.page_count(page) == len(added)
    for vid, color, payload in added:
        hit = pages.page_lookup(page, vid)
        assert hit is not None
        slot, data = hit
        assert data == payload
        assert slot.color == color


@given(records)
@settings(max_examples=50, deadline=None)
def test_slots_sorted_by_vid(entries):
    b = pages.PageBuilder()
    for vid, color, payload in entries:
        b.add(vid, color, payload)
    page = b.finalize()
    slots = pages.page_slots(page)
    vids = [s.vid for s in slots]
    assert vids == sorted(vids)


def test_lookup_missing_returns_none():
    b = pages.PageBuilder()
    b.add(5, 0, b"hello")
    page = b.finalize()
    assert pages.page_lookup(page, 4) is None
    assert pages.page_lookup(page, 6) is None


def test_two_way_growth_dense_packing():
    """Header+slots grow forward, heap backward; a full page wastes < one record."""
    b = pages.PageBuilder()
    payload = b"x" * 100
    vid = 0
    while b.add(vid, 0, payload):
        vid += 1
    page = b.finalize()
    util = pages.page_utilization(page)
    # free space must be smaller than one record+slot
    assert (1 - util) * pages.PAGE_SIZE < len(payload) + pages.SLOT_SIZE


def test_fixed_layout_fragmentation_grows_with_dim():
    """Fig. 6: fragmentation upper bound rises with dimensionality."""
    # record = d*4 vector + 260 adjacency bytes, page 4096
    utils = [
        pages.fixed_layout_utilization(d * 4 + 260)
        for d in (128, 512, 768, 960)
    ]
    frags = [1 - u for u in utils]
    assert frags[0] < 0.10            # SIFT-class: low fragmentation
    assert max(frags[1:]) > 0.20      # high-dim: severe fragmentation
    # GIST-like d=960: 4100B record spans 2 pages -> ~50% waste (paper: 52%)
    assert frags[3] == pytest.approx(0.5, abs=0.05)


@pytest.mark.parametrize("d", [128, 512, 960])
def test_velo_slotted_layout_fragmentation_small(d):
    """Fig. 6, the other side: VeloANN's compressed slotted pages stay
    nearly full at every dimensionality (under 12 % waste, tail page
    aside), where the fixed-size layout wastes up to half a page."""
    from repro.core import vamana
    from repro.core.dataset import make_dataset
    from repro.core.quant import RabitQuantizer
    from repro.core.store import VeloIndex

    ds = make_dataset(n=400, d=d, n_queries=10, k=5, seed=d)
    g = vamana.build_vamana(ds.base, R=16, L=24, two_pass=False, seed=0)
    qb = RabitQuantizer(d, seed=0).fit_encode(ds.base)
    index = VeloIndex(ds.base, g, qb)
    utils = [pages.page_utilization(p) for p in index.store.pages[:-1]]
    velo_frag = 1 - float(np.mean(utils))
    assert velo_frag < 0.12, velo_frag
