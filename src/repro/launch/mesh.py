"""Mesh construction. Functions only — importing this module never touches
jax device state (required so smoke tests/benches see a single device)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_engine_mesh(axis_name: str = "lun", num: int | None = None):
    """1-D mesh over all (or the first ``num``) devices for the ANNS engine.

    The vector DB treats every chip as one LUN group: the production mesh
    flattens pod x data x model into a single shard axis.
    """
    n = num or jax.device_count()
    return _make_mesh((n,), (axis_name,))


def make_mesh_for(num_devices: int, shape, axes):
    assert len(shape) == len(axes)
    return _make_mesh(tuple(shape), tuple(axes))
