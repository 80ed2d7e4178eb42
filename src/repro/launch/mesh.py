"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run entry point
sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """`jax.make_mesh` with Auto axes: the model code places arrays with
    `with_sharding_constraint`, which only refers to Auto axes (the default
    axis type of `jax.make_mesh` is Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
