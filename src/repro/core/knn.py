"""k-nearest-neighbours search (paper SIII-A), TPU-native.

Two paths with identical semantics:

* :func:`knn_blocked` - single-device blocked brute force.  Each row
  block makes one fused :func:`repro.kernels.ops.knn_topk` launch that
  folds every column tile into the running per-row candidate list while
  the (bm, bn) distance tile is still in VMEM - the analogue of the
  paper's block-pair/flatMap + heap-merge scheme, with the heap merge
  fused into the distance kernel so no distance tile reaches HBM.
  (:func:`knn_blocked_materializing` keeps the old
  compute-tile-then-top_k composition as the benchmark baseline and
  bit-identity witness.)

* :func:`knn_ring` - shard_map ring algorithm for a 1-D row decomposition.
  Each of the p shards holds an (n/p, D) slab; at step t the slab received
  from the ring neighbour is merged into the shard's candidate lists by
  one fused kernel launch (seeded with the previous step's lists) while
  `lax.ppermute` forwards the slab on.  After p steps every block pair has
  been computed exactly once - this replaces the paper's upper-triangular
  block enumeration (no (J,I) duplicates, no filter pass) and overlaps
  communication with compute.  Row counts that do not divide the mesh are
  padded with masked sentinel rows and the pad is stripped from the
  returned shards.

Distances returned are *squared* Euclidean; the neighbourhood graph stage
takes the sqrt (the paper builds G from Euclidean distances and squares
again after APSP).  Candidate lists are ranked by (distance, then column
index on ties); rows with fewer than k valid neighbours carry (+inf, -1)
tails.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops
from repro.kernels.ref import topk_by_index

_BIG = jnp.float32(jnp.inf)


def _fold_topk(best_d, best_i, new_d, new_i, k: int):
    """Merge running (b, k) top-k with a new (b, c) candidate block."""
    return topk_by_index(
        jnp.concatenate([best_d, new_d], axis=1),
        jnp.concatenate([best_i, new_i], axis=1), k,
    )


@functools.partial(jax.jit, static_argnames=("k", "block", "mode"))
@jax.named_scope("knn")
def knn_blocked(
    x: jax.Array, *, k: int, block: int = 1024, mode: str = "auto"
):
    """Exact kNN of every row of x (n, D) against all others.

    Returns (dists, idx), each (n, k), sorted ascending; squared distances.
    Self-matches are excluded.  One fused kernel launch per row block
    folds all column tiles in VMEM (tile sizes from the kNN autotuner,
    ``REPRO_KNN_TILES`` pins); ``block`` only sets how many rows each
    launch covers.
    """
    n, _ = x.shape
    block = min(block, n)
    n_orig = n
    if n % block:
        pad = block - n % block
        # sentinel rows: masked out of every merge via n_valid below
        x = jnp.pad(x, ((0, pad), (0, 0)))
        n += pad
    q = n // block

    def row_block(i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * block, block, 0)
        seed_d = jnp.full((block, k), _BIG)
        seed_i = jnp.full((block, k), -1, jnp.int32)
        return ops.knn_topk(
            xi, x, seed_d, seed_i,
            row0=i * block, col0=0, n_valid=n_orig, mode=mode,
        )

    ds, is_ = jax.lax.map(row_block, jnp.arange(q))
    return ds.reshape(n, k)[:n_orig], is_.reshape(n, k)[:n_orig]


@functools.partial(jax.jit, static_argnames=("k", "block", "mode"))
def knn_blocked_materializing(
    x: jax.Array, *, k: int, block: int = 1024, mode: str = "auto"
):
    """The pre-fusion kNN path: compute each (block, block) distance tile
    with the pairwise kernel, write it out, then top-k + fold in XLA.

    Kept as the benchmark baseline (``benchmarks/run.py --only knn``
    asserts the fused path beats it wall-clock at equal tiles and is
    bit-identical to it) - do not use it for real workloads.
    """
    n, _ = x.shape
    block = min(block, n)
    n_orig = n
    if n % block:
        pad = block - n % block
        # sentinel rows: far away, masked out of every top-k below
        x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=1e6)
        n += pad
    q = n // block

    def row_block(i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * block, block, 0)

        def col_step(j, carry):
            best_d, best_i = carry
            xj = jax.lax.dynamic_slice_in_dim(x, j * block, block, 0)
            d = ops.pairwise_sq_dists(xi, xj, mode=mode)
            # mask self distances and padded sentinel columns
            rows = i * block + jnp.arange(block)[:, None]
            cols = j * block + jnp.arange(block)[None, :]
            d = jnp.where((rows == cols) | (cols >= n_orig), _BIG, d)
            nd, ni = jax.lax.top_k(-d, k)
            return _fold_topk(best_d, best_i, -nd, cols[0][ni], k)

        init = (
            jnp.full((block, k), _BIG),
            jnp.zeros((block, k), jnp.int32),
        )
        return jax.lax.fori_loop(0, q, col_step, init)

    ds, is_ = jax.lax.map(row_block, jnp.arange(q))
    return ds.reshape(n, k)[:n_orig], is_.reshape(n, k)[:n_orig]


def knn_ring(
    x: jax.Array,
    *,
    k: int,
    mesh: Mesh,
    row_axis: str = "data",
    feat_axis: str | None = "model",
    split_axis: str | None = None,
    gather_features: bool = True,
    mode: str = "auto",
):
    """Distributed exact kNN over a 2-D (rows x features) sharding of x.

    Rows ride a `ppermute` ring over `row_axis` (each block pair computed
    exactly once - the TPU form of the paper's upper-triangular block
    enumeration); row counts that do not divide the mesh are padded with
    masked sentinel rows and the pad is stripped from the result.  The
    feature dimension is sharded over `feat_axis`; with
    ``gather_features`` (default, see EXPERIMENTS.md SPerf cell D) each
    device all-gathers its slab's features once up front (O(local x D)
    moved) and every ring step is one fused :func:`repro.kernels.ops
    .knn_topk` launch seeded with the previous step's candidate lists -
    the (local, local) distance block lives only in VMEM; otherwise the
    additive decomposition of ||x-y||^2 is psum-reduced per ring step
    (O(local^2) per step - the faithful-but-naive baseline, which does
    materialize the block).  `split_axis` (e.g. the "pod" axis) splits
    the ring walk: each replica group starts at a rotated offset and
    walks p/|split| of the ring, with a final cross-group top-k merge -
    this is how the multi-pod mesh parallelizes the kNN stage across
    pods.  Returns (dists, idx), row-sharded like x.
    """
    p = mesh.shape[row_axis]
    n_orig = x.shape[0]
    pad = -n_orig % p
    if pad:
        # sentinel rows so every shard holds the same local count; their
        # columns are masked via n_valid and their rows stripped below
        x = jnp.pad(x, ((0, pad), (0, 0)))
    fn = _make_knn_ring(
        mesh, n_orig, n_orig + pad, k, row_axis, feat_axis, split_axis,
        gather_features, mode,
    )
    d, i = fn(x)
    return (d[:n_orig], i[:n_orig]) if pad else (d, i)


@functools.lru_cache(maxsize=None)
def _make_knn_ring(
    mesh, n_orig, n, k, row_axis, feat_axis, split_axis, gather_features,
    mode,
):
    """The jitted ring of :func:`knn_ring`, built once per mesh, shape and
    parameters so that every later fit reuses its executable."""
    p = mesh.shape[row_axis]
    local = n // p
    perm = [(i, (i + 1) % p) for i in range(p)]
    n_split = mesh.shape[split_axis] if split_axis else 1
    assert p % n_split == 0
    steps = p // n_split

    @jax.named_scope("knn")
    def shard_fn(xs):
        # xs: (local, D_local) slab of this shard
        me = jax.lax.axis_index(row_axis)
        fused = gather_features or feat_axis is None
        if gather_features and feat_axis is not None:
            # one up-front feature gather; every distance block after
            # this is communication-free (vs a psum of the full
            # (local, local) block per ring step)
            xs = jax.lax.all_gather(xs, feat_axis, axis=1, tiled=True)
        buf, owner = xs, me
        if split_axis:
            # rotate each split group's starting slab by group*steps: one
            # extra permute hop per group level (log-style pre-rotation)
            g = jax.lax.axis_index(split_axis)
            for level in range(1, n_split):
                hop = [(i, (i + steps) % p) for i in range(p)]
                buf_r = jax.lax.ppermute(buf, row_axis, hop)
                owner_r = jax.lax.ppermute(owner, row_axis, hop)
                take = g >= level
                buf = jnp.where(take, buf_r, buf)
                owner = jnp.where(take, owner_r, owner)

        def step(t, carry):
            best_d, best_i, buf, owner = carry
            if fused:
                # fused merge: the received slab's columns fold into the
                # running lists inside the kernel, seeded from the
                # previous step - self-match and sentinel-row masking
                # happen in-kernel from the traced offsets
                best_d, best_i = ops.knn_topk(
                    xs, buf, best_d, best_i,
                    row0=me * local, col0=owner * local,
                    n_valid=n_orig, mode=mode,
                )
            else:
                rows = me * local + jnp.arange(local)[:, None]
                cols = owner * local + jnp.arange(local)[None, :]
                d = ops.pairwise_sq_dists(xs, buf, mode=mode)
                d = jax.lax.psum(d, feat_axis)
                dead = (rows == cols) | (cols >= n_orig)
                d = jnp.where(dead, _BIG, d)
                ci = jnp.where(
                    dead, -1, jnp.broadcast_to(cols, (local, local))
                )
                best_d, best_i = _fold_topk(best_d, best_i, d, ci, k)
            # rotate the slab around the ring; the permute overlaps with
            # the next step's distance computation
            buf = jax.lax.ppermute(buf, row_axis, perm)
            owner = jax.lax.ppermute(owner, row_axis, perm)
            return best_d, best_i, buf, owner

        init = (
            jnp.full((local, k), _BIG),
            jnp.full((local, k), -1, jnp.int32),
            buf,
            owner,
        )
        best_d, best_i, _, _ = jax.lax.fori_loop(0, steps, step, init)
        if split_axis:
            # merge the split groups' candidate lists
            all_d = jax.lax.all_gather(best_d, split_axis, axis=1, tiled=True)
            all_i = jax.lax.all_gather(best_i, split_axis, axis=1, tiled=True)
            best_d, best_i = topk_by_index(all_d, all_i, k)
        return best_d, best_i

    in_spec = P(row_axis, feat_axis) if feat_axis else P(row_axis, None)
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_spec,
        out_specs=(P(row_axis, None), P(row_axis, None)),
        check_vma=False,
    )
    return jax.jit(fn)
