"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state - the dry-run sets XLA_FLAGS before any jax
initialization and only then calls make_production_mesh().

Every mesh carries ``AxisType.Auto`` axes: sharding inside them is left
to the compiler (GSPMD) except where a shard_map body takes over.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh adds a leading
    2-pod axis (512 chips).  DP spans ("pod", "data"); TP/EP span "model"
    (ICI-local within a pod)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh for tests / laptop runs."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )
