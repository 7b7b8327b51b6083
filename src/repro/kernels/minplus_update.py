"""Pallas TPU kernel: fused min-plus update  O = min(G, C (x) R).

Phase 3 of blocked Floyd-Warshall relaxes the whole matrix against the
panel product:  G <- min(G, C (x) R)  with C (n, b) and R (b, n).  Composed
from the plain :mod:`repro.kernels.minplus` kernel this materializes the
full (n, n) min-plus product in HBM before the elementwise min; here the
output tile is seeded from G's tile at contraction step 0 and the rank-b
updates accumulate into it in VMEM, so the intermediate never exists.

Per-step VMEM footprint is bm*bk + bk*bn + 2*bm*bn floats (the G tile
rides in with the output tile), double-buffered for the streamed inputs:
about 2 MiB at the default 256-tiles, inside the 16 MiB scoped-VMEM
default of a v5e kernel.  HBM traffic drops from 3 n^2 + 2 n b to 2 n^2 + 2 n b
floats per diagonal iteration.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels.minplus import COMPILER_PARAMS, minplus_accumulate


def _minplus_update_kernel(g_ref, c_ref, r_ref, o_ref, *, unroll: int):
    # Same min-plus accumulation as the plain kernel; only the accumulator
    # seed differs (G's tile instead of +inf).
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = g_ref[...]

    o_ref[...] = minplus_accumulate(o_ref[...], c_ref, r_ref, unroll=unroll)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "unroll", "interpret")
)
def minplus_update(
    g: jax.Array,
    c: jax.Array,
    r: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    unroll: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """O[i,j] = min(G[i,j], min_k C[i,k] + R[k,j]).

    Shapes: g (m, n), c (m, k), r (k, n) -> (m, n).
    """
    m, n = g.shape
    m2, k = c.shape
    k2, n2 = r.shape
    assert (m, n) == (m2, n2) and k == k2, (g.shape, c.shape, r.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    unroll = min(unroll, bk)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{n}) min= ({m},{k})x({k},{n}) "
        f"not divisible by tile ({bm},{bn},{bk})"
    )
    assert bk % unroll == 0

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_minplus_update_kernel, unroll=unroll)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), g.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(g, c, r)
