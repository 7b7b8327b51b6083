"""Mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state: a process that fakes host devices sets
XLA_FLAGS before jax initializes and only then builds its mesh.

Every mesh carries ``AxisType.Auto`` axes: sharding inside them is left
to the compiler (GSPMD) except where a shard_map body takes over.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """A (data, model)-style mesh of the given shape and axis names."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )
