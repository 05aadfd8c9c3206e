"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax


def make_mesh_auto(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """(16, 16) ('data','model') per pod; (2, 16, 16) with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever this process actually has (1 CPU device in the container)."""
    n = len(jax.devices())
    return make_mesh_auto((1, n), ("data", "model"))

