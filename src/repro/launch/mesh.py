"""Production meshes.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches JAX device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any import.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate
    through ``with_sharding_constraint`` hints, the semantics the model
    code is written for (``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests/examples (uses however many local devices)."""
    return make_mesh((data, model), ("data", "model"))
