"""Mesh construction helpers (Auto axis types pinned for GSPMD, via
``repro.compat.make_mesh``)."""
from __future__ import annotations

from typing import Sequence

import jax

from repro import compat


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    return compat.make_mesh(shape, axes)


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Data-parallel axes: every axis that is not 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
