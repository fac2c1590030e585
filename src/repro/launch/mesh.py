"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256-class).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is the
DiLoCo worker boundary — inner training never communicates across it, the
HeLoCo outer exchange is the only traffic it carries.

Functions, not module-level constants: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    # Auto axes let GSPMD infer intermediate shardings
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small-device-count variant for unit tests (8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)
