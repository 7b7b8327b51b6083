"""Pallas TPU kernel: tropical (min-plus) matrix multiplication.

This is the workhorse of the blocked Floyd-Warshall APSP solver (paper
SIII-B): phases 2 and 3 are panel x panel min-plus products.  Min-plus is
not expressible on the MXU (the systolic array only does *,+), so this is a
VPU kernel: for each (bm, bn) output tile we loop over the contraction
dimension in VMEM, applying rank-1 `min(acc, a[:,k] + b[k,:])` updates.

Tiling: grid (m/bm, n/bn, k/bk) with the contraction innermost; the output
tile is initialized at k-step 0 and accumulated in place across k-steps
(the standard Pallas accumulation pattern).  VMEM footprint per step is
bm*bk + bk*bn + bm*bn floats, double-buffered for the streamed inputs,
plus one bk*bn copy of ``b`` (:func:`row_scratch`) - e.g. 256/1024/128
f32 = 4.6 MiB, inside the 16 MiB scoped-VMEM default the v5e compiler
applies to a kernel that sets no ``vmem_limit_bytes``.  Tiles the chip's
compiler accepts: ``bm`` a multiple of 8, ``bn`` and ``bk`` multiples of
128, or the whole dim.

Inside a tile the output is walked in register sub-tiles (:func:`subtile`)
that stay in vregs over the whole contraction; the contraction runs over
128-lane chunks of ``a``/``b`` sliced from the refs at aligned offsets
(the TPU lowering has no dynamic slice of a loaded value, and a dynamic
lane offset must be provably 128-aligned); within a chunk the rank-1
terms are unrolled statically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: contraction chunk: one vreg's worth of lanes
LANES = 128

#: rows of one vreg: the sublane extent of the chip's (8, 128) register tile
SUBLANES = 8

#: vector registers of one TPU core
VREGS = 64

#: most 8-row groups a register sub-tile holds: past 16 a narrow
#: sub-tile's spills outrun its overlap of lane broadcasts (a v5e
#: schedules 256 rows x 128 lanes at 2.115 bundles per vreg-term, 128
#: rows at 1.95)
MAX_GROUPS = 16

#: widest register sub-tile, in lanes: each lane broadcast of ``a``'s
#: column feeds this many lanes of output per term.  One 8-row group
#: 2048 lanes wide is a 16-vreg accumulator, which spills on a v5e as a
#: 16 x 1024 sub-tile does
MAX_RN = 1024

#: bundles per vreg-term of a v5e grid step's static schedule at a
#: (256, bn, 128) block, by the sub-tile's width in vregs (rn / 128);
#: the VALU floor is 0.5 (an add and a min over 4 slots)
BUNDLES_PER_VREG_TERM = {1: 1.95, 2: 1.176, 4: 0.823, 8: 0.6096}

#: grid semantics of the (rows, columns, contraction) min-plus grid
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def subtile(bm: int, bn: int) -> tuple[int, int]:
    """The (rm, rn) register sub-tile that walks a (bm, bn) output block.

    ``rn`` is the widest multiple of :data:`LANES` up to :data:`MAX_RN`
    that divides ``bn`` (a block whose width is no multiple of 128 is one
    sub-tile wide).  ``rm`` is the tallest multiple of :data:`SUBLANES`
    dividing ``bm`` with at most ``VREGS // v**2`` 8-row groups (and
    :data:`MAX_GROUPS`), v = rn / 128 the vregs of one group's row (a
    block whose height is no multiple of 8 is one sub-tile tall).

    Each group takes one lane broadcast per term (about 6 XLU slot-cycles
    of 3 per bundle) for 2 v VALU ops (of 4).  A narrow sub-tile is bound
    by the broadcasts, and wants many groups in flight to keep the XLUs
    busy; their accumulators spill, into store slots the schedule leaves
    idle.  A wide one is bound by the VALU, and its accumulator must
    leave the vreg file to the rows the scheduler loads ahead of the
    adds.  On a v5e (``BUNDLES_PER_VREG_TERM``): 128 rows at v = 1 and 2,
    32 at v = 4, 8 at v = 8.
    """
    if bn % LANES:
        rn = bn
    else:
        rn = max(w for w in range(LANES, min(bn, MAX_RN) + 1, LANES)
                 if bn % w == 0)
    if bm % SUBLANES:
        return bm, rn
    v = -(-rn // LANES)
    groups = max(1, min(MAX_GROUPS, VREGS // v**2))
    rm = max(SUBLANES * g for g in range(1, groups + 1)
             if bm % (SUBLANES * g) == 0)
    return rm, rn


def _row_stride(bk: int) -> int:
    """Rows one 128-lane column strip of ``b`` takes in the row copy:
    ``bk`` rounded up to whole 8-row tiles, so every strip starts aligned."""
    return -(-bk // SUBLANES) * SUBLANES


def row_scratch(bk: int, bn: int, dtype) -> list:
    """Scratch of a kernel over a (bk, bn) ``b`` tile: the tile's
    128-lane column strips stacked row-wise, so that each row of a strip
    can be read broadcast over the 8 sublanes in one load (a stride-0
    load needs a base ref 128 lanes wide).  None for a tile whose width
    is no multiple of 128."""
    if bn % LANES:
        return []
    return [pltpu.VMEM((bn // LANES * _row_stride(bk), LANES), dtype)]


def minplus_accumulate(o_ref, a_ref, b_ref, rows_ref=None, *,
                       interpret: bool):
    """o = min(o, A (x) B) in place: ``o_ref`` the (bm, bn) output tile,
    ``a_ref``/``b_ref`` the (bm, bk) x (bk, bn) contraction tiles,
    ``rows_ref`` the :func:`row_scratch` (None where it has none).

    The block is walked in (rm, rn) register sub-tiles (:func:`subtile`).
    Each is read from ``o_ref`` once, held in vregs as 8-row groups (one
    (8, rn) array each) through every term of the contraction, and
    written back once.  The contraction runs in chunks of :data:`LANES`
    columns of ``a`` (rows of ``b``) sliced at 128-aligned offsets; a
    contraction tile narrower than that, or not a multiple of it, is one
    chunk.  Inside a chunk every rank-1 term is unrolled statically: each
    row group adds the lane broadcast of its column of ``a`` to ``b``'s
    row, which is loaded from ``rows_ref`` already broadcast over the
    sublanes (a stride-0 load; ``interpret`` reads the row and broadcasts
    it, since the interpreter cannot express a stride-0 slice).  Every
    term is one rounded add and min is exact, so the result does not
    depend on the sub-tiling or the chunking.
    """
    bm, bn = o_ref.shape
    bk = a_ref.shape[1]
    rm, rn = subtile(bm, bn)
    rg = SUBLANES if rm % SUBLANES == 0 else rm
    lanes = LANES if bk % LANES == 0 else bk
    rows, cols = bm // rm, bn // rn
    kp = _row_stride(bk)

    if rows_ref is not None:
        for q in range(bn // LANES):
            rows_ref[pl.ds(q * kp, bk), :] = b_ref[:, pl.ds(q * LANES, LANES)]

    def b_row(i, c0):
        """Row i of ``b``'s columns c0:c0+rn, over the group's sublanes."""
        if rows_ref is None:
            return b_ref[pl.ds(i, 1), pl.ds(c0, rn)]
        strips = []
        for q in range(rn // LANES):
            r = (c0 // LANES + q) * kp + i
            strips.append(
                rows_ref[pl.ds(r, 1), :] if interpret
                else rows_ref[pl.ds(r, rg, stride=0), :]
            )
        return jnp.concatenate(strips, axis=1)

    def chunk(r0, c0, s, acc):
        a = [a_ref[pl.ds(r0 + g, rg), pl.ds(s, lanes)]
             for g in range(0, rm, rg)]
        acc = list(acc)
        for u in range(lanes):
            row = b_row(s + u, c0)
            for g, ag in enumerate(a):
                acc[g] = jnp.minimum(acc[g], ag[:, u:u + 1] + row)
        return tuple(acc)

    def tile(t, carry):
        # a lone sub-tile row or column gets a static offset: a dynamic
        # lane offset must be provably 128-aligned
        r0 = 0 if rows == 1 else pl.multiple_of((t // cols) * rm, rg)
        c0 = 0 if cols == 1 else pl.multiple_of((t % cols) * rn, rn)
        acc = tuple(o_ref[pl.ds(r0 + g, rg), pl.ds(c0, rn)]
                    for g in range(0, rm, rg))
        if bk == lanes:
            acc = chunk(r0, c0, 0, acc)
        else:
            acc = jax.lax.fori_loop(
                0, bk // lanes,
                lambda c, acc: chunk(
                    r0, c0, pl.multiple_of(c * lanes, lanes), acc),
                acc,
            )
        for g, x in zip(range(0, rm, rg), acc):
            o_ref[pl.ds(r0 + g, rg), pl.ds(c0, rn)] = x
        return carry

    jax.lax.fori_loop(0, rows * cols, tile, 0)


def _minplus_kernel(a_ref, b_ref, o_ref, *scratch, interpret: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    minplus_accumulate(o_ref, a_ref, b_ref, *scratch, interpret=interpret)


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
    """C[i,j] = min_k A[i,k] + B[k,j].  Shapes (m,k) x (k,n) -> (m,n).

    ``unroll`` is inert (the register sub-tile sets the schedule); it is
    kept, and checked to divide ``bk``, for the tile knobs' format."""
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
    kernel = functools.partial(_minplus_kernel, interpret=interpret)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=row_scratch(bk, bn, b.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(a, b)
