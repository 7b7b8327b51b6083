"""Pallas TPU kernel: masked sparse frontier relaxation (delta-stepping
sweep) over the padded-CSR kNN graph.

One bucketed delta-stepping / masked Bellman-Ford sweep for a batch of
``s`` sources against a fixed-shape adjacency::

    O[q, j] = min(D[q, j],  min_d  mask(D[q, nbr[j, d]]) + w[j, d])
    mask(x) = x            if x < hi
              +inf         otherwise

where ``nbr`` (n, deg) / ``w`` (n, deg) are the padded-CSR neighbour
lists (padded lanes carry ``w = +inf`` so they never win the min) and
``hi`` is the current bucket's upper bound: tentative distances at or
above ``hi`` are not allowed to propagate this sweep, which is both the
delta-stepping bucket discipline and the mask that keeps half-settled
long-range values from being charged as settled.

Layout: the gather ``D[q, nbr[j, d]]`` runs in XLA ahead of the kernel,
into a lane-dense (s, deg, n) block (node index on the lanes); the TPU
lowering has no general in-VMEM gather.  The kernel then streams that
block, the (deg, n) transposed weights and the (s, n) seed distances
through node tiles of width ``bn`` (a multiple of 128 on the chip) and
does the mask, the relax and both mins.  ``hi`` is a scalar operand in
SMEM rather than a static constant, and sssp_panel pads frontiers to
fixed shape, so the kernel jits once per (s, n, deg, bn) shape and
bucket progression never recompiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _frontier_kernel(hi_ref, g_ref, w_ref, d_ref, o_ref):
    # threshold mask -> relax -> min over each node's neighbour lanes ->
    # seed min, in this exact order; the CSR oracle (ref.frontier_relax_ref)
    # replays the same sequence so results are bit-identical (min is
    # exact, add is one rounding per term in both)
    hi = hi_ref[0]
    g = g_ref[...]                                  # (s, deg, bn)
    g = jnp.where(g < hi, g, jnp.inf)
    cand = jnp.min(g + w_ref[...][None, :, :], axis=1)  # (s, bn)
    o_ref[...] = jnp.minimum(d_ref[...], cand)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def frontier_relax(
    dist: jax.Array,
    nbr: jax.Array,
    w: jax.Array,
    hi: jax.Array,
    *,
    bn: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """One masked frontier sweep: O[q,j] = min(D[q,j],
    min_d where(D[q, nbr[j,d]] < hi) + w[j,d]).

    Shapes: dist (s, n), nbr (n, deg) int32, w (n, deg) -> (s, n).
    ``hi`` is a scalar (traced, so bucket progression does not recompile).
    """
    s, n = dist.shape
    n2, deg = nbr.shape
    assert n == n2 and w.shape == nbr.shape, (dist.shape, nbr.shape, w.shape)
    bn = min(bn, n)
    assert n % bn == 0, (
        f"n={n} not divisible by node tile bn={bn} "
        "(ops.frontier_relax pads to a tile multiple)"
    )
    hi = jnp.asarray(hi, dist.dtype).reshape(1)
    with jax.named_scope("gather"):
        g = jnp.take(dist, nbr.T, axis=1)           # (s, deg, n)

    # the innermost scope names the kernel's HLO instruction (and so its
    # op in a device trace): this one keeps the name frontier_relax
    with jax.named_scope("frontier_relax"):
        return pl.pallas_call(
            _frontier_kernel,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((s, deg, bn), lambda j: (0, 0, j)),
                pl.BlockSpec((deg, bn), lambda j: (0, j)),
                pl.BlockSpec((s, bn), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((s, bn), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((s, n), dist.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=interpret,
        )(hi, g, w.T, dist)
