"""Production mesh construction (functions only — importing this module
never touches jax device state).

Meshes use Auto axis types: the model code places arrays with sharding
constraints and `shard_map`, not with explicit-axis typing, which
`jax.make_mesh` now defaults to."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for multi-device correctness tests (subprocess runs)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
