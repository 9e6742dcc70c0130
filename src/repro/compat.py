"""Mesh and shard_map construction, in one place.

Every mesh in the repo has Auto axis types and every shard_map body runs
with replication (VMA) checking off: the step functions psum explicitly.
"""
from __future__ import annotations

import jax


def shard_map(fn, *, mesh, in_specs, out_specs):
    """jax.shard_map with replication/VMA checking off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes, *, devices=None):
    """jax.make_mesh with Auto axis types, over ``devices`` (default: all)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)
