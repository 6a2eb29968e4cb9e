"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (mandated — smoke tests must keep seeing 1 device).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU-host testing (requires forced device count)."""
    return make_mesh(shape, axes)
