"""Affinity co-placement invariants (paper §3.4)."""

import numpy as np
import pytest

from repro.core import placement
from repro.core.pages import page_lookup, page_records


def _payloads(n, rng, lo=40, hi=180):
    sizes = rng.integers(lo, hi, size=n)
    blobs = [bytes(rng.integers(0, 256, size=s, dtype=np.uint8)) for s in sizes]
    return lambda vid: blobs[vid]


def test_every_record_placed_exactly_once(rng):
    n = 500
    pf = _payloads(n, rng)
    affinity = {i: [i + 1, i + 2] for i in range(0, 300, 10)}
    layout = placement.layout_affinity(pf, n, affinity)
    seen = set()
    for page in layout.pages:
        for slot, payload in page_records(page):
            assert slot.vid not in seen
            seen.add(slot.vid)
            assert payload == pf(slot.vid)
    assert seen == set(range(n))


def test_vid_to_page_is_correct(rng):
    n = 400
    pf = _payloads(n, rng)
    affinity = {i: [i + 3, i + 7] for i in range(0, 200, 13)}
    layout = placement.layout_affinity(pf, n, affinity)
    for vid in range(n):
        page = layout.pages[layout.vid_to_page[vid]]
        assert page_lookup(page, vid) is not None


def test_affine_groups_share_page_and_color(rng):
    n = 600
    pf = _payloads(n, rng, lo=30, hi=60)  # small records: groups always fit
    affinity = {i: [i + 1, i + 2, i + 3] for i in range(0, 400, 20)}
    layout = placement.layout_affinity(pf, n, affinity)
    colocated = 0
    total = 0
    for p, group in affinity.items():
        members = [p] + group
        pids = {int(layout.vid_to_page[v]) for v in members}
        colors = {int(layout.colors[v]) for v in members}
        total += 1
        if len(pids) == 1:
            colocated += 1
            assert len(colors) == 1 and colors.pop() != 0
    assert colocated / total > 0.9  # splits only as a last resort


def test_non_affine_records_have_color_zero(rng):
    n = 300
    pf = _payloads(n, rng)
    affinity = {10: [11, 12]}
    layout = placement.layout_affinity(pf, n, affinity)
    members = {10, 11, 12}
    for vid in range(n):
        if vid not in members:
            assert layout.colors[vid] == 0


def test_sequential_and_shuffle_layouts_complete(rng):
    n = 250
    pf = _payloads(n, rng)
    layout = placement.layout_sequential(pf, n)
    assert sorted(
        s.vid for page in layout.pages for s, _ in page_records(page)
    ) == list(range(n))

    adjacency = np.arange(n * 4).reshape(n, 4) % n
    degrees = np.full(n, 4, dtype=np.int32)
    layout2 = placement.layout_block_shuffle(pf, n, adjacency.astype(np.int32), degrees)
    assert sorted(
        s.vid for page in layout2.pages for s, _ in page_records(page)
    ) == list(range(n))


def test_affinity_layout_improves_colocation(rng, small_ds, small_graph):
    """The point of §3.4: affine vertices co-located >> sequential layout."""
    g = small_graph
    pf = _payloads(g.n, rng, lo=60, hi=100)
    aff = g.affinity_ids(tau_scale=1.0, cap=8)
    lay_aff = placement.layout_affinity(pf, g.n, aff)
    lay_seq = placement.layout_sequential(pf, g.n)

    def coloc_fraction(layout):
        num = den = 0
        for p, group in aff.items():
            for v in group:
                den += 1
                if layout.vid_to_page[p] == layout.vid_to_page[v]:
                    num += 1
        return num / max(den, 1)

    assert coloc_fraction(lay_aff) > 2 * coloc_fraction(lay_seq)


# ------------------------------------------------ index size (Table 3)


@pytest.fixture(scope="module")
def gist_like_sizes():
    """Disk and memory bytes of velo and DiskANN over one GIST-shaped index
    (d=480, R=24), both at a 20 % buffer ratio."""
    from repro.core import baselines, vamana
    from repro.core.dataset import make_dataset
    from repro.core.quant import RabitQuantizer

    ds = make_dataset(n=300, d=480, n_queries=10, k=10, seed=0)
    g = vamana.build_vamana(ds.base, R=24, L=48, seed=0)
    qb = RabitQuantizer(480, seed=0).fit_encode(ds.base)
    cfg = baselines.SystemConfig(buffer_ratio=0.2)
    velo = baselines.build_system("velo", ds.base, g, qb, cfg)
    disk = baselines.build_system("diskann", ds.base, g, qb, cfg)
    return {
        "origin": ds.base.nbytes,
        "velo_disk": velo.disk_bytes(),
        "diskann_disk": disk.disk_bytes(),
        "velo_mem": velo.memory_bytes(),
        "diskann_mem": disk.memory_bytes(),
    }


@pytest.mark.parametrize("claim", [
    "velo_disk_much_smaller_than_diskann",
    "velo_disk_smaller_than_origin",
    "diskann_disk_amplifies_origin",
    "velo_mem_smaller",
])
def test_index_size_claims(claim, gist_like_sizes):
    """Table 3: velo's compressed disk index is several times smaller than
    DiskANN's and than the raw vectors, and so is its memory footprint."""
    s = gist_like_sizes
    ok = {
        "velo_disk_much_smaller_than_diskann":
            s["velo_disk"] < 0.25 * s["diskann_disk"],
        "velo_disk_smaller_than_origin": s["velo_disk"] < 0.5 * s["origin"],
        "diskann_disk_amplifies_origin": s["diskann_disk"] > s["origin"],
        "velo_mem_smaller": s["velo_mem"] < 0.5 * s["diskann_mem"],
    }[claim]
    assert ok, s
