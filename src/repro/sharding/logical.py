"""Mesh-axis helpers shared by the sharded pipeline stages.

A stage may shard one array dimension over several mesh axes at once (a
folded tuple such as ``("data", "model")``); these helpers give the size
and the row-major position of such a fold.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh


def mesh_axis_size(mesh: Mesh, axis) -> int:
    """Size of a (possibly folded tuple of) mesh axis(es)."""
    if isinstance(axis, (tuple, list)):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def folded_axis_index(axis):
    """axis_index generalized to folded tuples (row-major), for use inside
    shard_map bodies."""
    if isinstance(axis, (tuple, list)):
        idx = jax.lax.axis_index(axis[0])
        for a in axis[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(axis)
