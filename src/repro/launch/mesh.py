"""Device meshes.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh in the repo is built by :func:`make_mesh`, which marks each axis
``Auto``. ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
partitioned reshapes of core.distributed and every
``with_sharding_constraint`` (distributed.sharding, graphs.multi) are
refused.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (compiler-propagated)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def small_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """CPU-scale test mesh (requires xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
