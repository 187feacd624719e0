"""Mesh and shard_map constructors with the axis types the framework needs.

JAX's own ``jax.make_mesh`` defaults every axis to ``AxisType.Explicit``,
under which gathers with a sharded index operand raise ``ShardingTypeError``
(the paged KV cache's block-table gathers among them).  The framework's
sharding rules are written for ``Auto`` axes, so every mesh is built here:

  * ``make_mesh``   — ``jax.make_mesh`` with ``Auto`` axes.
  * ``device_mesh`` — ``jax.sharding.Mesh`` from an explicit device array,
    ``Auto`` axes.
  * ``shard_map``   — ``jax.shard_map`` with the replication check off by
    default (``check_vma=False``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["shard_map", "make_mesh", "device_mesh"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma,
    )


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names, axis_types=(AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


def device_mesh(device_array, axis_names):
    """``jax.sharding.Mesh`` from an explicit device ndarray, Auto axes."""
    return Mesh(device_array, axis_names, axis_types=(AxisType.Auto,) * len(axis_names))
