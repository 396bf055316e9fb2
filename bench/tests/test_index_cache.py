"""The index cache: a hit returns identical arrays, and a changed build-code
hash, seed or configuration builds again."""

import numpy as np

from bench import index_cache


def _builder(log):
    def build():
        log.append(1)
        rng = np.random.default_rng(len(log))
        return {"codes": rng.integers(0, 255, (64, 12), dtype=np.uint8),
                "norms": rng.standard_normal(64).astype(np.float32)}
    return build


def test_hit_is_identical_and_changed_hash_rebuilds(tmp_path):
    d = str(tmp_path)
    cfg = {"shape": {"n": 64, "d": 96}}
    log = []
    a, hit = index_cache.cached(cfg, 7, "hash-a", _builder(log), cache_dir=d)
    assert not hit and len(log) == 1
    b, hit = index_cache.cached(cfg, 7, "hash-a", _builder(log), cache_dir=d)
    assert hit and len(log) == 1
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
        assert a[k].dtype == b[k].dtype
    c, hit = index_cache.cached(cfg, 7, "hash-b", _builder(log), cache_dir=d)
    assert not hit and len(log) == 2
    assert not np.array_equal(a["norms"], c["norms"])
    _, hit = index_cache.cached(cfg, 8, "hash-b", _builder(log), cache_dir=d)
    assert not hit and len(log) == 3
    _, hit = index_cache.cached({"shape": {"n": 65, "d": 96}}, 7, "hash-b",
                                _builder(log), cache_dir=d)
    assert not hit and len(log) == 4


def test_code_hash_follows_file_bytes(tmp_path):
    p = tmp_path / "vamana.py"
    p.write_text("R = 32\n")
    h0 = index_cache.code_hash([str(p)])
    assert index_cache.code_hash([str(p)]) == h0
    p.write_text("R = 33\n")
    assert index_cache.code_hash([str(p)]) != h0


def test_oldest_entries_go_past_the_size_cap(tmp_path):
    d = str(tmp_path)
    log = []
    for seed in range(4):
        index_cache.cached({"s": 1}, seed, "h", _builder(log), cache_dir=d, max_bytes=2500)
    assert len(list(tmp_path.glob("*.pkl"))) == 2
    _, hit = index_cache.cached({"s": 1}, 3, "h", _builder(log), cache_dir=d, max_bytes=2500)
    assert hit
