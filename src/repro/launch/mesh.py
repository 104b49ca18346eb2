"""Production meshes.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and the dry-run
needs to set XLA_FLAGS before that happens).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 4, model: int = 2):
    """Small CPU mesh for tests/benchmarks (requires the host-device flag)."""
    n = len(jax.devices())
    data = min(data, max(1, n // model))
    return jax.make_mesh((data, model), ("data", "model"))
