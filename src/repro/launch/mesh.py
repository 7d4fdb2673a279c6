"""Production mesh definitions (deliverable e).

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def _auto_mesh(shape, axes):
    """Mesh whose axes are all Auto: the partitioner places what is not
    pinned, and `with_sharding_constraint` (launch/activations.py) may
    name them.  `jax.make_mesh` defaults to Explicit axes, which refuse
    such constraints."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips over ("data", "model").
    Multi-pod: 2x16x16 = 512 chips over ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that carry the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def make_smoke_mesh():
    """1-device mesh for CPU smoke tests (same axis names as single-pod)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_abstract_mesh(shape, axes):
    """Device-free mesh of the given shape, for sharding rules and specs."""
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=(AxisType.Auto,) * len(axes))
