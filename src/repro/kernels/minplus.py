"""Pallas TPU kernel: tropical (min-plus) matrix multiplication.

This is the workhorse of the blocked Floyd-Warshall APSP solver (paper
SIII-B): phases 2 and 3 are panel x panel min-plus products.  Min-plus is
not expressible on the MXU (the systolic array only does *,+), so this is a
VPU kernel: for each (bm, bn) output tile we loop over the contraction
dimension in VMEM, applying rank-1 `min(acc, a[:,k] + b[k,:])` updates.

Tiling: grid (m/bm, n/bn, k/bk) with the contraction innermost; the output
tile is initialized at k-step 0 and accumulated in place across k-steps
(the standard Pallas accumulation pattern).  VMEM footprint per step is
bm*bk + bk*bn + bm*bn floats, double-buffered for the streamed inputs -
e.g. 256/256/256 f32 = 768 KiB (1.3 MiB buffered), inside the 16 MiB
scoped-VMEM default the v5e compiler applies to a kernel that sets no
``vmem_limit_bytes``.  Tiles the chip's compiler accepts: ``bm`` a
multiple of 8, ``bn`` and ``bk`` multiples of 128, or the whole dim.

Inside a tile the contraction runs over 128-lane chunks of ``a``/``b``
sliced from the refs at aligned offsets (the TPU lowering has no dynamic
slice of a loaded value, and a dynamic lane offset must be provably
128-aligned); within a chunk the rank-1 terms are unrolled statically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: contraction chunk: one vreg's worth of lanes
LANES = 128

#: grid semantics of the (rows, columns, contraction) min-plus grid
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def minplus_accumulate(acc, a_ref, b_ref, *, unroll: int):
    """acc = min(acc, A (x) B) for the (bm, bk) x (bk, bn) tiles in the refs.

    The contraction runs in chunks of :data:`LANES` columns of ``a`` (rows
    of ``b``), each sliced from the refs at a 128-aligned offset; a
    contraction tile narrower than that, or not a multiple of it, is one
    chunk.  Inside a chunk every rank-1 term ``a[:, u] + b[u, :]`` is
    unrolled statically, and each run of ``unroll`` terms is min-reduced
    into a partial before it meets the accumulator.  Every term is one
    rounded add and min is exact, so the result does not depend on the
    chunking or ``unroll``.
    """
    bk = a_ref.shape[1]
    lanes = LANES if bk % LANES == 0 else bk

    def chunk(a, b, acc):
        for g in range(0, lanes, unroll):
            part = a[:, g:g + 1] + b[g:g + 1, :]
            for u in range(g + 1, min(g + unroll, lanes)):
                part = jnp.minimum(part, a[:, u:u + 1] + b[u:u + 1, :])
            acc = jnp.minimum(acc, part)
        return acc

    if bk == lanes:
        return chunk(a_ref[...], b_ref[...], acc)

    def body(c, acc):
        s = pl.multiple_of(c * lanes, lanes)
        return chunk(a_ref[:, pl.ds(s, lanes)], b_ref[pl.ds(s, lanes), :], acc)

    return jax.lax.fori_loop(0, bk // lanes, body, acc)


def _minplus_kernel(a_ref, b_ref, o_ref, *, unroll: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    o_ref[...] = minplus_accumulate(o_ref[...], a_ref, b_ref, unroll=unroll)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "unroll", "interpret")
)
def minplus(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    unroll: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """C[i,j] = min_k A[i,k] + B[k,j].  Shapes (m,k) x (k,n) -> (m,n)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    unroll = min(unroll, bk)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k})x({k},{n}) not divisible by tile ({bm},{bk},{bn})"
    )
    assert bk % unroll == 0

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_minplus_kernel, unroll=unroll)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(a, b)
