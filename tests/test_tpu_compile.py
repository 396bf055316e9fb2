"""Compile the search path's kernels and jitted steps for a TPU v5e chip.

Nothing runs: each case lowers and compiles for a described (not attached)
v5e chip, at real widths and with ``interpret=False``, so the chip's
compiler refuses here what it would refuse there — a kernel that needs more
VMEM than a core has, a tile that is not aligned, a program over HBM.

The topology is described inside a module-scoped fixture and never while a
module is imported: only one process may load the TPU library, and the test
workers all import this file.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import distance
from repro.kernels.binary_ip import estimate_dist2
from repro.kernels.binary_ip.ops import binary_ip
from repro.kernels.int4_dist import int4_dist2
from repro.velo.index import synthetic_specs
from repro.velo.scan_search import scan_search

WIDTHS = [96, 128, 960]    # DEEP, SIFT, GIST
B_Q, N_ROWS = 128, 256     # one full default tile of queries, two of rows
N_SCALE = 1_000_000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means: not describable
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; the Pallas kernel must be in it."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("d", WIDTHS)
def test_int4_dist_compiles(one_chip, d):
    S = functools.partial(_spec, one_chip)
    _compile(
        functools.partial(int4_dist2, interpret=False),
        S((B_Q, d), jnp.float32), S((N_ROWS, d // 2), jnp.uint8),
        S((N_ROWS,), jnp.float32), S((N_ROWS,), jnp.float32),
    )


@pytest.mark.parametrize("d", WIDTHS)
def test_binary_ip_compiles(one_chip, d):
    S = functools.partial(_spec, one_chip)
    _compile(
        functools.partial(binary_ip, interpret=False),
        S((B_Q, d), jnp.float32), S((N_ROWS, d // 8), jnp.uint8),
    )
    _compile(
        functools.partial(estimate_dist2, interpret=False),
        S((B_Q, d), jnp.float32), S((N_ROWS, d // 8), jnp.uint8),
        S((N_ROWS,), jnp.float32), S((N_ROWS,), jnp.float32),
    )


def _beam_step(*args):
    return distance._pallas_beam_fn()(*args, bucket=64, interpret=False)


def test_fused_beam_step_compiles(one_chip):
    """The served path's device step: gather + binary estimate + visited mask
    + top-L merge + frontier, over a 1M-row table, 8 queries."""
    S = functools.partial(_spec, one_chip)
    n, d, B, F, L = N_SCALE, 96, 8, 32, 64
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    _compile(
        _beam_step,
        S((B, d), f32), S((n, d // 8), u8), S((n,), f32), S((n,), f32),
        S((B, F), i32), S((B,), i32), S((B,), i32),
        S((B, 8), i32), S((B, 8), f32), S((B,), i32), S((B, 8), i32),
        S((B, L), f32), S((B, L), i32),
        S((B, n + 1), jnp.bool_), S((B, n + 1), jnp.bool_),
    )


def test_scan_search_compiles(one_chip):
    """The 1M x 96 device-resident scan (256 queries): level-1 kernel over
    streamed chunks, int4 rerank, top-10."""
    specs = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        synthetic_specs(N_SCALE, 96, 1),
    )
    compiled = _compile(
        functools.partial(scan_search, k=10, rerank=512, interpret=False),
        specs, _spec(one_chip, (256, 96), jnp.float32),
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 70e6  # the tables really are in HBM
    assert mem.temp_size_in_bytes < 16 * 2**30 - mem.argument_size_in_bytes
