"""Pallas TPU kernel: blocked squared-Euclidean pairwise distances (kNN).

The paper's kNN stage (SIII-A) delegates `cdist` blocks to BLAS; on TPU the
dominant term -2*X@Y^T of ||x-y||^2 = ||x||^2 + ||y||^2 - 2<x,y> *is* an MXU
matmul, so unlike the Spark/CPU version this stage is MXU-bound.  Each grid
step computes one (bm, bn) distance tile from a (bm, bd) x (bn, bd) pair of
point blocks, accumulating over feature chunks so arbitrarily large D
streams through VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import DOT_PRECISION


def _pd_kernel(x_ref, y_ref, o_ref, *, last_step: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (bm, bd)
    y = y_ref[...].astype(jnp.float32)  # (bn, bd)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)          # (bm, 1)
    y2 = jnp.sum(y * y, axis=1, keepdims=True)          # (bn, 1)
    xy = jax.lax.dot_general(                           # MXU: (bm, bn)
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=DOT_PRECISION,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] += x2 + y2.T - 2.0 * xy

    @pl.when(pl.program_id(2) == last_step)
    def _clamp():
        o_ref[...] = jnp.maximum(o_ref[...], 0.0)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bd", "interpret")
)
def pairwise_sq_dists(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 512,
    bn: int = 512,
    bd: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Squared distances between rows of x (m, D) and y (n, D) -> (m, n)."""
    m, d = x.shape
    n, d2 = y.shape
    assert d == d2, (x.shape, y.shape)
    bm, bn, bd = min(bm, m), min(bn, n), min(bd, d)
    assert m % bm == 0 and n % bn == 0 and d % bd == 0, (
        f"({m},{d})x({n},{d}) not divisible by tile ({bm},{bn},{bd})"
    )
    grid = (m // bm, n // bn, d // bd)
    kernel = functools.partial(_pd_kernel, last_step=grid[2] - 1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bd), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, y)
