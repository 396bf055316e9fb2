"""Production meshes.  Functions, not module constants — importing this module
never touches jax device state (the dry-run sets the device-count XLA flag
before any jax import)."""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(n_devices: int = 8, model_par: int = 2):
    """Small virtual mesh for CI-grade distributed tests."""
    data = n_devices // model_par
    return _make_mesh((data, model_par), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"


def n_devices(mesh) -> int:
    import numpy as np

    return int(np.prod(mesh.devices.shape))
