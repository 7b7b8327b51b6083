"""Pallas TPU kernel: fused pairwise distances + per-row top-k merge.

The kNN stage's blocked brute force used to compute each (bm, bn)
squared-distance tile with the pairwise kernel, write it to HBM, and only
then run ``lax.top_k`` + a concat-re-top-k fold in XLA — at n = 10^6 that
round-trips ~n^2 * 4 bytes of distances through HBM to keep only k values
per row.  This kernel is the paper's block-pair + heap-merge scheme
(SIII-A) folded onto the TPU memory hierarchy: each grid step computes one
(bm, bn) distance tile on the MXU *and* merges it into a running per-row
(bm, k) candidate list (distances + global column indices) while the tile
is still in VMEM.  The distance tile never exists in HBM.

Structure mirrors :mod:`repro.kernels.minplus_update` (the repo's seeded
accumulator pattern): grid (m/bm, n/bn) with the column dimension
innermost and sequential; the output candidate list is the accumulator,
seeded from the incoming (seed_d, seed_i) lists at column step 0 and
revisited in place across column tiles.  Seeding makes the kernel
composable — `knn_blocked` seeds with (+inf, -1) empty lists, `knn_ring`
seeds each ring step with the previous step's lists, and a streaming
caller could seed with candidates from an earlier shard of columns.

Selection rule: candidates are ranked by (distance, then global column
index), so a tie is won by the smaller column whatever order the columns
arrive in — by tile, by seeded call, or by ring step.  That makes the
result independent of the (bm, bn) tiling and of the chaining order (a
ring over a mesh returns the one-chip lists), and bit-identical to the
chunked oracle (:func:`repro.kernels.ref.knn_topk_ref`) for any chunking:
min and compare are exact, and the distance tile is computed with the
identical
x2 + y2 - 2<x,y> op sequence over the full feature depth in both.

The squared row norms come in as operands, computed once per call by
the wrapper (:func:`repro.kernels.ref.sq_norms`), so kernel and oracle
add the very same norms.

Masking is done in-kernel from a (3,) int32 SMEM operand (row0, col0, hi):
a lane is dead when its global column equals its global row (self-match)
or is >= hi (padded columns / columns beyond the caller's valid range).
Dead lanes carry (+inf, -1); rows with fewer than k live candidates
return (+inf, -1) in the unfilled slots.  The offsets are traced array
operands (like the frontier kernel's ``hi``) so ring steps with varying
owners do not recompile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import DOT_PRECISION

#: index carried by masked / unfilled candidate slots
PAD_IDX = -1


def _knn_topk_kernel(
    meta_ref, x_ref, y_ref, x2_ref, y2_ref, sd_ref, si_ref, od_ref, oi_ref
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _seed():
        od_ref[...] = sd_ref[...]
        oi_ref[...] = si_ref[...]

    row0 = meta_ref[0]
    col0 = meta_ref[1]
    hi = meta_ref[2]

    x = x_ref[...].astype(jnp.float32)  # (bm, D)
    y = y_ref[...].astype(jnp.float32)  # (bn, D)
    bm, bn = x.shape[0], y.shape[0]
    k = od_ref.shape[1]

    # one (bm, bn) distance tile on the MXU — same op sequence as the
    # oracle: x2 + y2 - 2<x,y> over the full feature depth, clamped at
    # zero (one rounding per term, so bit-identical)
    xy = jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=DOT_PRECISION,
        preferred_element_type=jnp.float32,
    )
    d = jnp.maximum(x2_ref[...] + y2_ref[...] - 2.0 * xy, 0.0)

    i = pl.program_id(0)
    rows = row0 + i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = col0 + j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    dead = (rows == cols) | (cols >= hi)
    d = jnp.where(dead, jnp.inf, d)
    idx = jnp.where(dead, PAD_IDX, cols)

    # merge the tile into the running list: k extraction steps over the
    # (bm, k + bn) candidate stream [running list | tile columns].  Each
    # step takes the (value, column index)-minimum, then retires its
    # position (the first one, should a column come twice).
    vals = jnp.concatenate([od_ref[...], d], axis=1)    # (bm, k + bn)
    idxs = jnp.concatenate([oi_ref[...], idx], axis=1)
    width = k + bn
    pos0 = jax.lax.broadcasted_iota(jnp.int32, (bm, width), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)

    def step(t, carry):
        vals, pos, out_d, out_i = carry
        v = jnp.min(vals, axis=1, keepdims=True)        # (bm, 1)
        # retired positions carry pos = width and take no part; at step
        # t < k at most t < width are retired, so each row has a live
        # tie and sel picks exactly one live position.  Once only +inf
        # is left, the live entries are dead lanes / empty seed slots,
        # all with index PAD_IDX
        tie = (vals == v) & (pos < width)
        iv = jnp.min(
            jnp.where(tie, idxs, jnp.iinfo(jnp.int32).max),
            axis=1, keepdims=True,
        )
        p = jnp.min(
            jnp.where(tie & (idxs == iv), pos, width), axis=1, keepdims=True
        )
        sel = pos == p
        out_d = jnp.where(lane == t, v, out_d)
        out_i = jnp.where(lane == t, iv, out_i)
        return (
            jnp.where(sel, jnp.inf, vals),
            jnp.where(sel, width, pos),
            out_d,
            out_i,
        )

    out_d = jnp.zeros((bm, k), jnp.float32)
    out_i = jnp.zeros((bm, k), jnp.int32)
    _, _, out_d, out_i = jax.lax.fori_loop(
        0, k, step, (vals, pos0, out_d, out_i)
    )
    od_ref[...] = out_d
    oi_ref[...] = out_i


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def knn_topk(
    x: jax.Array,
    y: jax.Array,
    x2: jax.Array,
    y2: jax.Array,
    seed_d: jax.Array,
    seed_i: jax.Array,
    meta: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused k-nearest merge of y's rows into x's candidate lists.

    x (m, D), y (n, D), their squared row norms x2 (m, 1) and y2 (1, n),
    seed_d/seed_i (m, k), meta (3,) int32 [row0, col0, hi] ->
    (dists (m, k) f32, idx (m, k) int32), sorted by (distance, column).
    ``m``/``n`` must be tile multiples — :func:`repro.kernels.ops
    .knn_topk` pads and strips.  On the chip ``bn`` is a multiple of 128
    or the whole (padded) ``n``.
    """
    m, dfeat = x.shape
    n, d2 = y.shape
    assert dfeat == d2, (x.shape, y.shape)
    k = seed_d.shape[1]
    assert seed_d.shape == (m, k) and seed_i.shape == (m, k), (
        seed_d.shape, seed_i.shape,
    )
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (
        f"({m},{dfeat})x({n},{dfeat}) not divisible by tile ({bm},{bn}) "
        "(ops.knn_topk pads to tile multiples)"
    )
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _knn_topk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, dfeat), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dfeat), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.int32),
        ),
        # row tiles are independent; column tiles accumulate sequentially
        # into the revisited candidate list
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(meta, x, y, x2, y2, seed_d.astype(jnp.float32),
      seed_i.astype(jnp.int32))
