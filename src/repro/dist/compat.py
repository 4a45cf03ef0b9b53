"""The jax surface every mesh program goes through.

The repo runs on jax 0.9: ``jax.shard_map`` with ``check_vma``, and
``jax.make_mesh``, whose axes default to Explicit there.  Sharding
constraints and shard_map programs in this repo assume Auto axes, so every
mesh is built here.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis_name):
    return jax.lax.axis_size(axis_name)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))
