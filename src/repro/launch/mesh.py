"""Production mesh definition (a function — importing never touches devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "POD_SHAPE", "MULTI_POD_SHAPE"]

POD_SHAPE = (16, 16)              # one v5e pod slice: 256 chips
MULTI_POD_SHAPE = (2, 16, 16)     # 2 pods over DCN: 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
