"""Mesh and shard_map construction in one place.

Every mesh and ``shard_map`` in the repo goes through these two helpers so
the axis types and the replication check are chosen once: meshes are built
with ``AxisType.Auto`` axes, and ``shard_map`` bodies run with
``check_vma=False`` (the routing bodies insert their own psums at the
paper's Table-2 aggregation points).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    shape, axes = tuple(shape), tuple(axes)
    kwargs = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes), **kwargs)


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
