"""``make_mesh`` makes every mesh: entry points, tests and the dry run.

Axes are ``AxisType.Auto``: the cells mix GSPMD with ``jax.shard_map``
islands, and under Explicit axes (``jax.make_mesh``'s default) the dense
backward is refused with "Contracting dimensions are sharded".

Functions, not module-level constants: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape=None, axes: tuple[str, ...] = ("data",), devices=None):
    """Mesh over ``devices`` (default: all of the default backend's)."""
    devs = np.array(jax.devices()) if devices is None else np.asarray(devices)
    if shape is None:
        shape = (devs.size,)
    return jax.make_mesh(shape, axes, devices=devs.reshape(-1),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, devices=None):
    """16×16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, devices)

