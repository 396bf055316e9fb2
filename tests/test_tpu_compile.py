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
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distance
from repro.kernels.binary_ip import estimate_dist2
from repro.kernels.binary_ip.ops import binary_ip
from repro.kernels.int4_dist import int4_dist2
from repro.velo import dist_search
from repro.velo.index import ext_row_words, synthetic_specs
from repro.velo.scan_search import scan_search

WIDTHS = [96, 128, 960]    # DEEP, SIFT, GIST
B_Q, N_ROWS = 128, 256     # one full default tile of queries, two of rows
N_SCALE = 1_000_000


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


@pytest.fixture(scope="module")
def gist_cell_scan(one_chip):
    """The GIST cell's scan (250,000 x 960, 256 queries, C = 512, chunk
    32768), compiled for the described chip."""
    specs = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        synthetic_specs(250_000, 960, 1),
    )
    return _compile(
        functools.partial(scan_search, k=10, rerank=512, interpret=False,
                          chunk=32768),
        specs, _spec(one_chip, (256, 960), jnp.float32),
    )


def test_scan_search_compiles_gist_cell(gist_cell_scan):
    """The GIST cell's scan: its select stage runs on packed keys in groups,
    and the whole call's temporaries fit beside the tables in HBM."""
    mem = gist_cell_scan.memory_analysis()
    assert mem.argument_size_in_bytes > 150e6
    assert mem.temp_size_in_bytes < 16 * 2**30 - mem.argument_size_in_bytes


def test_scan_rerank_reads_level2_in_place(gist_cell_scan):
    """The GIST cell's rerank reads the level-2 table where it lies: one
    instruction reads the ``ext_rows`` parameter (the candidates' row
    gather), and no instruction copies a whole (n+1)-row table."""
    text = gist_cell_scan.as_text()
    entry = text[text.index("\nENTRY"):]
    table = rf"u32\[250001,{ext_row_words(960)}\]"
    params = re.findall(rf"(%[\w.\-]+) = {table}\S* parameter\(", entry)
    assert len(params) == 1
    readers = [ln for ln in entry.splitlines()[1:]
               if params[0] + "," in ln or params[0] + ")" in ln]
    assert len(readers) == 1 and " copy" not in readers[0]
    assert not re.search(r"= \w+\[250001,\d+\]\S* copy(-start)?\(", text)


def test_sharded_scan_compiles_gist1m(topo):
    """The sharded GIST1M cell's program on the host's four chips: 4 shards
    of 250,000 x 960, each with its sentinel row, 256 replicated queries.
    Each chip runs the scan kernel on its shard and the merge all-gathers;
    each holds only its shard's tables."""
    shards, per, d = 4, 250_000, 960
    mesh = Mesh(np.asarray(topo.devices[:shards]), (dist_search.AXIS,))
    split, whole = NamedSharding(mesh, P(dist_search.AXIS)), NamedSharding(mesh, P())
    specs = synthetic_specs(shards * (per + 1) - 1, d, 1)  # it adds the last sentinel
    specs = type(specs)(**{
        f: _spec(whole if f in dist_search.REPLICATED else split, s.shape, s.dtype)
        for f, s in vars(specs).items()})
    program = dist_search.sharded_scan_program(mesh, k=10, rerank=512, chunk=32768,
                                               interpret=False)
    compiled = program.lower(specs, _spec(split, (shards,), jnp.int32),
                             _spec(whole, (256, d), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    assert f"jit_{dist_search.PROGRAM}" in text
    mem = compiled.memory_analysis()           # per chip
    assert 150e6 < mem.argument_size_in_bytes < 200e6
    assert mem.temp_size_in_bytes < 16 * 2**30 - mem.argument_size_in_bytes
