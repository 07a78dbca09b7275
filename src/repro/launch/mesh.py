"""Production meshes. A FUNCTION (never a module-level constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (the sharding
    rules place arrays with ``with_sharding_constraint``, not explicit
    axis types)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist, as (data, model) = (N, 1)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
