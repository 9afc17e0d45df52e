"""Shared Pallas kernel utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# XLA lays a 1-D 32-bit array out in tiles of 1024 elements on TPU; a
# 1-D block must be a multiple of it or Mosaic refuses the operand layout
LANE_TILE = 1024


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, multiple: int) -> int:
    return cdiv(a, multiple) * multiple


def pad_to(x: jnp.ndarray, multiple: int, axis: int = 0, fill=0) -> jnp.ndarray:
    size = x.shape[axis]
    target = cdiv(size, multiple) * multiple
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=fill)


def default_interpret() -> bool:
    """Pallas kernels target TPU; on CPU (this container) run the kernel
    body in interpret mode — identical semantics, Python execution."""
    return jax.default_backend() != "tpu"


def resolve_use_pallas(flag=None) -> bool:
    """Resolve an engine's `use_pallas` argument: an explicit True/False
    wins; `None` defers to the REPRO_USE_PALLAS environment variable
    (1/true/yes/on, case-insensitive), default off. Lets CI flip the whole
    engine matrix onto the kernel paths without threading a flag through
    every entry point."""
    if flag is not None:
        return bool(flag)
    import os
    return os.environ.get("REPRO_USE_PALLAS", "").strip().lower() in (
        "1", "true", "yes", "on")
