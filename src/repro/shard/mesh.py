"""Device meshes the CFPQ engines can shard over.

Since JAX 0.7, ``jax.make_mesh`` gives every axis the ``Explicit`` type by
default, and ``with_sharding_constraint`` — which the sharded closures
use to place their packed operand copies (``MeshPlan.closure_specs``) —
accepts only ``Auto`` axes.  Every mesh the repo builds goes through
:func:`make_mesh`, which types all axes ``Auto``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes=("data", "model"), *, devices=None):
    """A mesh of ``shape`` over ``axes``, every axis ``Auto``.  ``devices``
    defaults to ``jax.devices()`` (so this starts the backend)."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def explicit_axes(mesh) -> tuple[str, ...]:
    """Names of ``mesh``'s axes that are not ``Auto`` (empty when the
    sharded closures can run on it)."""
    types = getattr(mesh, "axis_types", None) or ()
    return tuple(
        name for name, t in zip(mesh.axis_names, types) if t != AxisType.Auto
    )
