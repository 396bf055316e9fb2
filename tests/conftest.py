"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see 1 CPU device;
only launch/dryrun.py (and subprocesses spawned by distributed tests) set the
512-device flag."""

import numpy as np
import pytest

from repro.core import dataset as dataset_mod
from repro.core import vamana as vamana_mod
from repro.core.quant import RabitQuantizer


@pytest.fixture(scope="session")
def small_ds():
    return dataset_mod.make_dataset(n=1500, d=64, n_queries=60, k=10, seed=0)


@pytest.fixture(scope="session")
def small_graph(small_ds):
    return vamana_mod.build_vamana(
        small_ds.base, R=20, L=40, batch_size=256, seed=0
    )


@pytest.fixture(scope="session")
def small_qb(small_ds):
    return RabitQuantizer(small_ds.dim, seed=0).fit_encode(small_ds.base)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


def _int4_three_tables(qb):
    """Level 2 as three device tables, the host ``QuantizedBase``'s
    ``ext_codes`` / ``ext_lo`` / ``ext_step`` with the sentinel row (codes 0,
    lo 0, step 1) appended, and the refine formula over them: the rerank's
    arithmetic before ``DeviceIndex`` packed level 2 into ``ext_rows``."""
    import jax.numpy as jnp

    codes = jnp.asarray(np.concatenate(
        [qb.ext_codes, np.zeros((1, qb.ext_codes.shape[1]), np.uint8)]))
    lo = jnp.asarray(np.concatenate([qb.ext_lo, [0.0]]).astype(np.float32))
    step = jnp.asarray(np.concatenate([qb.ext_step, [1.0]]).astype(np.float32))

    def refine(ids, qr):
        d = qr.shape[1]
        packed = codes[ids].astype(jnp.int32)
        lo4 = (packed & 0xF).astype(jnp.float32)
        hi4 = ((packed >> 4) & 0xF).astype(jnp.float32)
        codes4 = jnp.stack([lo4, hi4], axis=-1).reshape(*ids.shape, d)
        x = codes4 * step[ids][..., None] + lo[ids][..., None]
        diff = qr[:, None, :] - x
        return jnp.einsum("bmd,bmd->bm", diff, diff)

    return refine


@pytest.fixture(scope="session")
def int4_three_tables():
    """``qb`` -> refine(ids (B, M), qr (B, d)) -> (B, M) dist^2 by the
    three-table formula (see ``_int4_three_tables``)."""
    return _int4_three_tables
