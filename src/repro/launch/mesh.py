"""Device meshes.

Functions, not module-level constants — importing this module never touches
jax device state (jax locks the device count on first backend init).

Every mesh in the repo comes from ``make_mesh``.  Its axes are
``AxisType.Auto``: the model code places arrays with
``with_sharding_constraint`` and leaves the rest to the SPMD partitioner,
which is what ``jnp.take`` on a vocab-sharded embedding and ``shard_map``
outside ``jax.set_mesh`` need (jax >= 0.9 makes ``jax.make_mesh`` default
to Explicit axes, under which both raise).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """Mesh of ``shape`` over ``axes`` with Auto axis types.

    ``devices`` defaults to ``jax.devices()``; pass a subset (or the
    devices of a described topology) to build the mesh over those."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 chips (pod, data, model); 'pod' is the
    DCI-connected outer data axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
