"""Mesh construction (functions, not module constants: importing this module
never touches jax device state)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes. The model code places
    activations with ``with_sharding_constraint`` and lets XLA propagate the
    rest, which ``Explicit`` axes (the ``make_mesh`` default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
