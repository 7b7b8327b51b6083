"""Pallas TPU kernel: masked sparse frontier relaxation (delta-stepping
sweep) over the padded-CSR kNN graph.

One bucketed delta-stepping / masked Bellman-Ford sweep for a batch of
``s`` sources against a fixed-shape adjacency, nodes-major::

    O[j, q] = min(D[j, q],  min_d  mask(D[nbr[j, d], q]) + w[j, d])
    mask(x) = x            if x < hi
              +inf         otherwise

where ``D`` (n, s) holds the tentative distances with the sources on the
lanes, ``nbr`` (n, deg) / ``w`` (n, deg) are the padded-CSR neighbour
lists (padded lanes carry ``w = +inf`` so they never win the min) and
``hi`` is the current bucket's upper bound: tentative distances at or
above ``hi`` are not allowed to propagate this sweep, which is both the
delta-stepping bucket discipline and the mask that keeps half-settled
long-range values from being charged as settled.

Layout: the gather ``D[nbr[j, d], :]`` runs in XLA ahead of the kernel,
as a row gather into a (deg, n, s) block: each gathered slot is one
contiguous s-float row (the embedding-lookup pattern), and the block's
two minor dims (n, s) tile as (8, 128) with no padding at s = 128; the
TPU lowering has no general in-VMEM gather.  The kernel then streams
(deg, bn, s) blocks of it, (bn, deg) tiles of the weights and (bn, s)
tiles of the seed distances through node tiles of ``bn`` rows and does
the mask, the relax and both mins, one neighbour slot at a time, each
slot's weight column broadcast along the source lanes.  ``hi`` is a
scalar operand in SMEM rather than a static constant, and sssp_panel
pads frontiers to fixed shape, so the kernel jits once per (n, s, deg,
bn) shape and bucket progression never recompiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _frontier_kernel(hi_ref, g_ref, w_ref, d_ref, o_ref):
    # per element: threshold mask -> relax -> min over the neighbour
    # slots -> seed min; the CSR oracle (ref.frontier_relax_ref) replays
    # the same terms, so results are bit-identical (min is exact and
    # order-free, the add is one rounding per term in both)
    hi = hi_ref[0]
    w = w_ref[...]                                  # (bn, deg)
    acc = None
    for d in range(g_ref.shape[0]):
        g = g_ref[d]                                # (bn, s)
        cand = jnp.where(g < hi, g, jnp.inf) + w[:, d:d + 1]
        acc = cand if acc is None else jnp.minimum(acc, cand)
    o_ref[...] = jnp.minimum(d_ref[...], acc)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def frontier_relax(
    dist: jax.Array,
    nbr: jax.Array,
    w: jax.Array,
    hi: jax.Array,
    *,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One masked frontier sweep: O[j,q] = min(D[j,q],
    min_d where(D[nbr[j,d], q] < hi) + w[j,d]).

    Shapes: dist (n, s), nbr (n, deg) int32, w (n, deg) -> (n, s).
    ``hi`` is a scalar (traced, so bucket progression does not recompile).
    """
    n, s = dist.shape
    n2, deg = nbr.shape
    assert n == n2 and w.shape == nbr.shape, (dist.shape, nbr.shape, w.shape)
    bn = min(bn, n)
    assert n % bn == 0, (
        f"n={n} not divisible by node tile bn={bn} "
        "(ops.frontier_relax pads to a tile multiple)"
    )
    hi = jnp.asarray(hi, dist.dtype).reshape(1)
    with jax.named_scope("gather"):
        # CSR indices lie in [0, n): no clamp or fill select on the block
        g = dist.at[nbr.T].get(mode="promise_in_bounds")   # (deg, n, s)

    # the innermost scope names the kernel's HLO instruction (and so its
    # op in a device trace): this one keeps the name frontier_relax
    with jax.named_scope("frontier_relax"):
        return pl.pallas_call(
            _frontier_kernel,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((deg, bn, s), lambda j: (0, j, 0)),
                pl.BlockSpec((bn, deg), lambda j: (j, 0)),
                pl.BlockSpec((bn, s), lambda j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bn, s), lambda j: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((n, s), dist.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=interpret,
        )(hi, g, w, dist)
