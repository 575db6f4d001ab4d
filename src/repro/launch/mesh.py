"""Production mesh construction (functions only — importing this module
never touches jax device state). Every mesh axis is `AxisType.Auto`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods).

    `pod`  — DCN tier: pure DP (LM), extra index shards (ANN)
    `data` — ICI: batch DP + FSDP
    `model`— ICI: TP / EP / index shards
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (8 virtual devices)."""
    return _make_mesh(shape, axes)
