"""Production meshes.  Functions, not module constants -- importing this
module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use).

Every mesh has Auto axes: jax.make_mesh defaults to Explicit axes, on which
the with_sharding_constraint calls of models.sharding.constrain fail.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Single-device mesh for smoke tests / local serving."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
