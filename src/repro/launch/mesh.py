"""Mesh construction. Importing this module never touches jax device state;
``make_production_mesh`` is a function per the dry-run contract.
"""

from __future__ import annotations

from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips, ``pod`` is the
    DCN/loose boundary (BSP across it, or the Local-SGD axis)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices are available."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    return make_mesh((data, model), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
