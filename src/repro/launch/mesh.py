"""Mesh construction (functions only — importing this module never touches
jax device state). Every mesh axis is `AxisType.Auto`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (8 virtual devices)."""
    return _make_mesh(shape, axes)
