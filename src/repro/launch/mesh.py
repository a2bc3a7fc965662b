"""Production meshes.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count is locked at first jax init, and the
smoke tests must see one CPU device while the dry-run sees 512 placeholder
host devices)."""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices a test process has."""
    return jax.make_mesh((data, model), ("data", "model"))
