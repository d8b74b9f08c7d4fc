"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "mesh_chips"]


def _make(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod ("data", "model"); 2 pods adds an outer "pod"
    data-parallel axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return _make(shape, axes)


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
