"""The sharded scan (``dist_search.ShardedScan``) against its plain reference
(``dist_search_ref``) and against the per-shard scans merged on one device,
on four CPU devices, at GIST's width (d = 960).

JAX fixes its device count when it starts, and the suite's workers each run
with one CPU device, so the four-device run is a subprocess of its own
(this file run as a script); the tests judge what it measured.  A corpus of
4 x 1,200 rows, C = 64 and chunk 512 below the shard size run the chunked
``lax.scan`` with the grouped select and the tail's ``top_k``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

SEED = 20240917
SHARDS, PER, D = 4, 1200, 960
N = SHARDS * PER
QUERIES, K, C, CHUNK = 64, 10, 64, 512

# (a): the device's rerank is float32 like the reference's refine_batch; only
# the order of the 960-term sums differs, ~1e-6 relative.  A bf16 rerank
# misses by ~100x.
DIST_RTOL = 1e-5
# (b): the device's level-1 estimate is bf16, so at a shard's C-th place it
# may keep a different candidate among estimates within bf16's rounding of
# each other, and that candidate may reach the top-10.
AGREE_MIN = 0.97


def _corpus():
    rng = np.random.default_rng(SEED)
    centres = rng.standard_normal((N // 40, D)).astype(np.float32) * np.float32(2 / np.sqrt(D))
    base = centres[rng.integers(0, len(centres), N)] + np.float32(0.3) * rng.standard_normal(
        (N, D), dtype=np.float32)
    queries = centres[rng.integers(0, len(centres), QUERIES)] + np.float32(0.3) * (
        rng.standard_normal((QUERIES, D), dtype=np.float32))
    return base, queries.astype(np.float32)


def _dist_gap(qb, queries, ids, d2) -> float:
    """Largest relative gap between a returned distance and the reference's
    int4 distance of the returned id (inf where an id lies outside)."""
    from repro.velo import dist_search_ref

    want = dist_search_ref.int4_dist2(qb, queries, ids).astype(np.float64)
    gap = np.abs(d2 - want) / np.maximum(np.abs(want), 1e-12)
    return float(np.nan_to_num(gap, nan=np.inf).max())


def _measure() -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.quant import RabitQuantizer
    from repro.velo import dist_search, dist_search_ref
    from repro.velo.index import from_host
    from repro.velo.scan_search import scan_search

    assert jax.device_count() == SHARDS, jax.devices()
    base, queries = _corpus()
    qb = RabitQuantizer(D, seed=SEED % 1000).fit_encode(base)
    entry = dist_search.ShardedScan(qb, jax.devices(), k=K, rerank=C, chunk=CHUNK)

    def run():
        ids, d2 = entry.search(queries)
        return np.asarray(ids), np.asarray(d2)

    ids, d2 = run()
    r_ids, _ = dist_search_ref.search(qb, queries, SHARDS, k=K, rerank=C)
    agree = np.mean([len(set(a) & set(b)) / K for a, b in zip(ids, r_ids)])

    # the same per-shard scans on one device, merged by merge_topk
    g_all, d_all = [], []
    for off, part in zip(np.asarray(entry.offsets), dist_search.shard_rows(qb, SHARDS)):
        li, ld = scan_search(from_host(part), jnp.asarray(queries), k=K, rerank=C,
                             chunk=CHUNK)
        g, d = dist_search.mask_local_topk(li, ld, jnp.int32(off))
        g_all.append(g)
        d_all.append(d)
    m_ids, m_d2 = dist_search.merge_topk(jnp.concatenate(g_all, axis=1),
                                         jnp.concatenate(d_all, axis=1), K)
    m_ids, m_d2 = np.asarray(m_ids), np.asarray(m_d2)

    out = {
        "dist_gap": _dist_gap(qb, queries, ids, d2),
        "agree": float(agree),
        "same_as_merged": dist_search_ref.topk_agree(ids, d2, m_ids, m_d2),
        "hlo_names_program": f"jit_{dist_search.PROGRAM}" in entry.hlo_text(QUERIES),
        "hlo_scopes_merge": dist_search.MERGE_SCOPE in entry.hlo_text(QUERIES),
        "shard_rows": sorted(int(s.data.shape[0])
                             for s in entry.index.binary_codes.addressable_shards),
        "shard_devices": len({s.device for s in entry.index.binary_codes.addressable_shards}),
        "planted": {},
    }

    host, offsets = dist_search.host_shards(qb, SHARDS)
    planted = {"offsets_shifted": (host, (offsets + PER) % N)}
    dropped = dict(host)   # shard 0's sentinel row dropped; the rows after it move up
    for f in dist_search.ROW_FIELDS + ("adjacency",):
        a = host[f]
        dropped[f] = np.concatenate([a[:PER], a[PER + 1:], a[-1:]])
    planted["sentinel_dropped"] = (dropped, offsets)
    for name, (h, o) in planted.items():
        entry.index, entry.offsets = dist_search.place(h, o, entry.mesh)
        f_ids, f_d2 = run()
        out["planted"][name] = {
            "dist_gap": _dist_gap(qb, queries, f_ids, f_d2),
            "same_as_merged": dist_search_ref.topk_agree(f_ids, f_d2, m_ids, m_d2),
        }
    return out


@pytest.fixture(scope="module")
def measured():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count={SHARDS}").strip())
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join([src, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_device_holds_one_shard(measured):
    assert measured["shard_devices"] == SHARDS
    assert measured["shard_rows"] == [PER + 1] * SHARDS   # its rows and its sentinel


def test_distances_are_the_int4_distances_of_the_ids(measured):
    """(a) every returned dist2 is the reference's int4 distance of that id."""
    assert measured["dist_gap"] <= DIST_RTOL


def test_top10_agrees_with_the_reference(measured):
    """(b) mean top-10 agreement with the plain reference."""
    assert measured["agree"] >= AGREE_MIN


def test_equals_per_shard_scans_merged_on_one_device(measured):
    """(c) the shard_map program equals the per-shard scans and merge_topk
    on one device, up to exact distance ties."""
    assert measured["same_as_merged"]


@pytest.mark.parametrize("fault", ["offsets_shifted", "sentinel_dropped"])
def test_planted_fault_is_caught(measured, fault):
    """(d) a wrong placement fails (a) or (c)."""
    got = measured["planted"][fault]
    assert got["dist_gap"] > DIST_RTOL or not got["same_as_merged"]


def test_program_name_and_merge_scope(measured):
    """The trace finds the program as ``jit_sharded_scan`` and the merge by
    its scope."""
    assert measured["hlo_names_program"] and measured["hlo_scopes_merge"]


def test_scan_rotates_queries_in_float32():
    """(a) on the chip: the TPU's default precision rounds float32 matmul
    operands to bf16, so the query rotation that the rerank's distances
    start from asks for HIGHEST.  The CPU computes float32 either way, so
    the lowered program is what can be checked here."""
    import jax
    import jax.numpy as jnp

    from repro.velo.index import synthetic_specs
    from repro.velo.scan_search import scan_search

    text = scan_search.lower(synthetic_specs(300, D, 1),
                             jax.ShapeDtypeStruct((8, D), jnp.float32), k=K, rerank=C,
                             use_kernel=False).as_text()
    rotation = [ln for ln in text.splitlines() if "dot_general" in ln
                and f"tensor<{D}x{D}xf32>" in ln]
    assert len(rotation) == 1 and "precision = [HIGHEST, HIGHEST]" in rotation[0]


def test_reference_level1_is_estimate_batch():
    """The reference's level-1 estimates for a block of queries equal
    ``RabitQuantizer.estimate_batch`` query by query."""
    from repro.core.quant import RabitQuantizer
    from repro.velo import dist_search_ref

    base, queries = _corpus()
    qb = RabitQuantizer(D, seed=3).fit_encode(base[:600])
    pqs = [RabitQuantizer.prepare_query(qb, q) for q in queries[:5]]
    rows = slice(100, 400)
    got = dist_search_ref.estimates(qb, pqs, rows)
    for j, pq in enumerate(pqs):
        want = RabitQuantizer.estimate_batch(qb, pq, qb.binary_codes[rows],
                                             qb.norms[rows], qb.ip_bar[rows])
        np.testing.assert_allclose(got[j], want, rtol=1e-5, atol=1e-5)


def test_reference_smallest_ids_ties_to_lower_id():
    from repro.velo import dist_search_ref

    v = np.array([3.0, 1.0, 2.0, 1.0, 2.0, 0.5], np.float32)
    assert dist_search_ref.smallest_ids(v, 3).tolist() == [5, 1, 3]
    assert dist_search_ref.smallest_ids(v, 4).tolist() == [5, 1, 3, 2]
    assert dist_search_ref.smallest_ids(v, 9).tolist() == [5, 1, 3, 2, 4, 0]


if __name__ == "__main__":
    print(json.dumps(_measure()), flush=True)
