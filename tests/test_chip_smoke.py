"""chip_smoke.py's guards and the compile-cache helper, on the CPU."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_config():
    """Restore the process's compile-cache settings after the test."""
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_smoke_refuses_a_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""                      # no result line
    assert "'cpu'" in err                 # names the platform it found


def test_topk_agree_allows_exact_ties_only(smoke):
    ids = np.array([[1, 2, 3, 4]])
    d = np.array([[0.1, 0.2, 0.2, 0.5]], np.float32)
    assert smoke.topk_agree(ids, d, ids, d)
    swapped = np.array([[1, 3, 2, 4]])    # 2 and 3 tie at 0.2
    assert smoke.topk_agree(swapped, d, ids, d)
    last = np.array([[1, 2, 3, 9]])       # k-th place: tie beyond the list
    assert smoke.topk_agree(last, d, ids, d)
    other = np.array([[7, 2, 3, 4]])      # no tie at 0.1
    assert not smoke.topk_agree(other, d, ids, d)
    assert not smoke.topk_agree(ids, d + np.float32(0.01), ids[:, ::-1], d)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == compile_cache.DEFAULT_DIR
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))  # as at start-up
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_counter_counts_backend_compiles():
    import jax.numpy as jnp

    with compile_cache.CompileCounter() as cc:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert cc.compiles >= 1
