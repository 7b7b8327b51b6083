"""Add/min rate of the vector unit on a VMEM-resident block.

The min-plus kernels do one add and one min per term on the VPU, and no
VPU peak is published, so ``bench/peaks.json`` carries a derived upper
bound.  This kernel checks it from below: a (128, 128) float32 block
stays in VMEM while every element runs ``acc = min(acc + x, y)`` for
``iters`` steps (two ops per element per step; the chain cannot be folded
or hoisted).  If the measured rate ever beats the table, the table is
wrong and a roofline share built on it could read above 100%.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 128
LANES = 128


#: steps written out per loop trip (Mosaic unrolls no loop partially)
UNROLL = 8


def _kernel(x_ref, y_ref, o_ref, *, iters: int):
    x = x_ref[...]
    y = y_ref[...]

    def trip(_, acc):
        for _ in range(UNROLL):
            acc = jnp.minimum(acc + x, y)
        return acc

    o_ref[...] = jax.lax.fori_loop(0, iters // UNROLL, trip, x)


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def addmin(x, y, *, iters: int, interpret: bool = False):
    return pl.pallas_call(
        functools.partial(_kernel, iters=iters),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, y)


def measure(*, iters: int = 10_000_000, reps: int = 5,
            interpret: bool = False) -> float:
    """-> add/min ops per second, best of ``reps`` timed calls (each ends
    in ``block_until_ready``; the first call compiles and is not timed)."""
    x = jnp.full((ROWS, LANES), 1.0, jnp.float32)
    y = jnp.full((ROWS, LANES), 3.0, jnp.float32)
    addmin(x, y, iters=iters, interpret=interpret).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        addmin(x, y, iters=iters, interpret=interpret).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2.0 * ROWS * LANES * (iters // UNROLL * UNROLL) / best
