"""Production mesh definitions.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax import).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips (one pod); 2x16x16 = 512 chips (two pods).

    When the process exposes more devices than the mesh needs (the dry-run
    boots 512 host devices for both meshes), the first `n` are used.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == need:
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    if len(devs) < need:
        raise RuntimeError(f"need {need} devices, have {len(devs)} — "
                           "set XLA_FLAGS=--xla_force_host_platform_device_count")
    return Mesh(np.array(devs[:need]).reshape(shape), axes)


def make_local_mesh() -> Mesh:
    """Degenerate 1x1 mesh with production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
