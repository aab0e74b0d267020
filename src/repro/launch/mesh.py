"""Production meshes.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def device_mesh(devices=None):
    """``(data=1, model=n)`` mesh over ``devices`` (default: all of
    ``jax.devices()``), in the order given — the chip runs' mesh."""
    devs = list(devices if devices is not None else jax.devices())
    return jax.sharding.Mesh(
        np.array(devs).reshape(1, len(devs)), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_test_mesh(data: int = 2, model: int = 4):
    """Small host-device mesh for integration tests (8 devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size_of(mesh) -> int:
    s = 1
    for a in dp_axes_of(mesh):
        s *= mesh.shape[a]
    return s
