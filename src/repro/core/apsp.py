"""Blocked communication-avoiding all-pairs shortest paths (paper SIII-B).

The algorithm is the Solomonik et al. / Venkataraman blocked Floyd-Warshall
the paper casts into Spark.  Per diagonal index I (q = n/b iterations):

  Phase 1   D = FW(G[I,I])                       (in-VMEM kernel)
  Phase 2   R = min(R, D (x) R)  (row panel)     (fused in-place min-plus)
            C = min(C, C (x) D)  (column panel)
  Phase 3   G = min(G, C (x) R)                  (rank-b min-plus update)

All three min-plus phases run fused Pallas kernels (seeded accumulation,
see repro.kernels.minplus_panel / minplus_update): no phase materializes
a min-plus product intermediate in HBM, and tile sizes are picked per
problem shape at trace time by repro.kernels.autotune.

Because D has a zero diagonal, the Phase-3 update subsumes writing back D,
R and C (min-plus idempotency) - a fusion the Spark version cannot express
(it must yield per-block RDD updates) but single-program SPMD can.

Two realizations:

* :func:`apsp_blocked` - single device; oracle + laptop scale.
* :func:`apsp_sharded` - shard_map over a ("data", "model") mesh with a 2-D
  tile decomposition.  Panels are broadcast with masked psums: the block
  row crosses the "data" axis (O(b * n / p_model) per device), the block
  column crosses "model".  Per iteration the communicated volume is
  O(n*b) against O(n^2 b) compute - the communication-avoiding ratio the
  paper inherits from the HPC schedule.

Fault tolerance: :func:`apsp_sharded` exposes segment execution (run
iterations [lo, hi) on explicit state) so the driver can checkpoint the
sharded matrix every K panels - the TPU analogue of the paper's
every-10-iterations RDD lineage checkpoint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ops
from repro.sharding.logical import folded_axis_index, mesh_axis_size


# ----------------------------------------------------------------- local --


@functools.partial(jax.jit, static_argnames=("block", "mode"))
@jax.named_scope("apsp")
def apsp_blocked_segment(
    g: jax.Array, lo, hi, *, block: int = 512, mode: str = "auto"
):
    """Run diagonal iterations [lo, hi) of single-device blocked
    Floyd-Warshall on `g` (the evolving (n, n) matrix, inf = no edge).

    Segment execution is the fault-tolerance unit: the pipeline engine
    checkpoints `g` between segments and a resumed run re-enters at the
    recorded iteration.  lo/hi may be traced (jnp.int32) so one compiled
    executable serves every segment."""
    n = g.shape[0]
    block = min(block, n)
    assert n % block == 0, (n, block)

    def iteration(i, g):
        off = i * block
        with jax.named_scope("diag"):
            d = jax.lax.dynamic_slice(g, (off, off), (block, block))
            d = ops.floyd_warshall(d, mode=mode)
        with jax.named_scope("panels"):
            r = jax.lax.dynamic_slice(g, (off, 0), (block, n))
            c = jax.lax.dynamic_slice(g, (0, off), (n, block))
            # Phase 2 fused: in-place panel updates min(R, D (x) R) /
            # min(C, C (x) D) - no (b, n) min-plus intermediate
            r = ops.minplus_panel_row(d, r, mode=mode)
            c = ops.minplus_panel_col(c, d, mode=mode)
        # Phase 3 fused: min(G, C (x) R) without the (n, n) intermediate
        with jax.named_scope("update"):
            return ops.minplus_update(g, c, r, mode=mode)

    return jax.lax.fori_loop(lo, hi, iteration, g)


def apsp_blocked(g: jax.Array, *, block: int = 512, mode: str = "auto"):
    """Single-device blocked Floyd-Warshall. g: (n, n), inf = no edge."""
    n = g.shape[0]
    q = n // min(block, n)
    return apsp_blocked_segment(
        g, jnp.int32(0), jnp.int32(q), block=block, mode=mode
    )


# ------------------------------------------------------------- sharded ----


def _masked_bcast_rows(local, off_in_shard, own, b, axis):
    """Extract b rows starting at off_in_shard from the owning shard and
    broadcast them along `axis` via a masked psum."""
    sl = jax.lax.dynamic_slice_in_dim(local, off_in_shard, b, axis=0)
    sl = jnp.where(own, sl, 0.0)
    return jax.lax.psum(sl, axis)


def _masked_bcast_cols(local, off_in_shard, own, b, axis):
    sl = jax.lax.dynamic_slice_in_dim(local, off_in_shard, b, axis=1)
    sl = jnp.where(own, sl, 0.0)
    return jax.lax.psum(sl, axis)


@jax.named_scope("apsp")
def _apsp_shard_body(
    g_loc, lo, hi, *, b, nr, nc, pd, pm, data_axis, model_axis, mode,
    split_panels=False,
):
    """Run diagonal iterations [lo, hi) on the local (nr, nc) tile.

    split_panels: Phase-2 panel products are redundantly computed by every
    rank of a row/column group in the baseline (the faithful port of the
    paper's one-block-one-task mapping).  When set, each rank computes a
    1/p slice of the panel and the group all-gathers the result - panel
    FLOPs drop p-fold for one extra (b x n/p) gather per iteration (see
    EXPERIMENTS.md SPerf, apsp iteration 1).  Callers leaving it unset
    get the roofline decision (:func:`repro.kernels.ops.auto_split_panels`).
    """
    di = folded_axis_index(data_axis)
    mi = folded_axis_index(model_axis)

    def iteration(i, g_loc):
        off = i * b
        # --- panel broadcasts (the only communication) ---
        r_owner = off // nr          # data-group owning the block row
        c_owner = off // nc          # model-group owning the block column
        with jax.named_scope("exchange"):
            row = _masked_bcast_rows(
                g_loc, off - r_owner * nr, di == r_owner, b, data_axis
            )                        # (b, nc) on every device
            col = _masked_bcast_cols(
                g_loc, off - c_owner * nc, mi == c_owner, b, model_axis
            )                        # (nr, b)
            # diagonal block, replicated everywhere: slice it out of `row`
            loc_off = jnp.clip(off - c_owner * nc, 0, nc - b)
            sl = jax.lax.dynamic_slice_in_dim(row, loc_off, b, axis=1)
            diag = jax.lax.psum(
                jnp.where(mi == c_owner, sl, 0.0), model_axis
            )
        # --- Phase 1: FW on the diagonal block (replicated compute) ---
        with jax.named_scope("diag"):
            diag = ops.floyd_warshall(diag, mode=mode)
        # --- Phase 2: panel updates ---
        if split_panels and b % pd == 0 and b % pm == 0:
            # fused split panels: each rank updates its 1/p slice in place
            # (min(slice, dslice (x) panel) via the seeded Phase-3 kernel)
            # and the group gathers - still no min-plus intermediate
            bs_r = b // pd
            bs_c = b // pm
            with jax.named_scope("panels"):
                dslice = jax.lax.dynamic_slice_in_dim(
                    diag, di * bs_r, bs_r, 0
                )
                rseed = jax.lax.dynamic_slice_in_dim(
                    row, di * bs_r, bs_r, 0
                )
                row_part = ops.minplus_update(
                    rseed, dslice, row, mode=mode
                )                                           # (b/pd, nc)
            with jax.named_scope("exchange"):
                row = jax.lax.all_gather(
                    row_part, data_axis, axis=0, tiled=True
                )                                           # (b, nc)
            with jax.named_scope("panels"):
                dslice = jax.lax.dynamic_slice_in_dim(
                    diag, mi * bs_c, bs_c, 1
                )
                cseed = jax.lax.dynamic_slice_in_dim(
                    col, mi * bs_c, bs_c, 1
                )
                col_part = ops.minplus_update(
                    cseed, col, dslice, mode=mode
                )                                           # (nr, b/pm)
            with jax.named_scope("exchange"):
                col = jax.lax.all_gather(
                    col_part, model_axis, axis=1, tiled=True
                )                                           # (nr, b)
        else:
            # Phase 2 fused in-place panel updates (no intermediate)
            with jax.named_scope("panels"):
                row = ops.minplus_panel_row(diag, row, mode=mode)  # (b, nc)
                col = ops.minplus_panel_col(col, diag, mode=mode)  # (nr, b)
        # --- Phase 3: fused rank-b min-plus update of the local tile ---
        with jax.named_scope("update"):
            return ops.minplus_update(g_loc, col, row, mode=mode)

    return jax.lax.fori_loop(lo, hi, iteration, g_loc)


def make_apsp_segment(
    mesh: Mesh,
    *,
    n: int,
    b: int,
    data_axis: str = "data",
    model_axis: str = "model",
    mode: str = "auto",
    split_panels: bool | None = None,
):
    """Build segment_fn(g, lo, hi) -> g running APSP iterations [lo, hi).

    g is the (n, n) matrix sharded P(data_axis, model_axis).  Segments let
    the caller checkpoint between them (fault-tolerance unit).

    split_panels: None (default) consults the roofline decision in
    :func:`repro.kernels.ops.auto_split_panels` (env-pinnable via
    ``REPRO_SPLIT_PANELS``); True/False pin it at the call site.
    """
    pd, pm = mesh_axis_size(mesh, data_axis), mesh_axis_size(mesh, model_axis)
    if split_panels is None:
        split_panels = ops.auto_split_panels(n, b, pd, pm)
    nr, nc = n // pd, n // pm
    assert n % pd == 0 and n % pm == 0
    assert nr % b == 0 or b % nr == 0
    assert b <= nr and b <= nc, (
        f"block {b} must fit in a local tile ({nr}, {nc})"
    )
    assert nr % b == 0 and nc % b == 0

    body = functools.partial(
        _apsp_shard_body,
        b=b, nr=nr, nc=nc, pd=pd, pm=pm,
        data_axis=data_axis, model_axis=model_axis, mode=mode,
        split_panels=split_panels,
    )

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(), P()),
        out_specs=P(data_axis, model_axis),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def cached_apsp_segment(
    mesh: Mesh,
    *,
    n: int,
    b: int,
    data_axis: str = "data",
    model_axis: str = "model",
    mode: str = "auto",
    split_panels: bool | None = None,
):
    """:func:`make_apsp_segment` memoized per (mesh, n, b, ...) so the
    pipeline engine can request the segment fn once per segment without
    rebuilding (and re-jitting) the shard_map each time."""
    return make_apsp_segment(
        mesh, n=n, b=b, data_axis=data_axis, model_axis=model_axis,
        mode=mode, split_panels=split_panels,
    )


def apsp_sharded(
    g: jax.Array,
    mesh: Mesh,
    *,
    b: int | None = None,
    segment: int | None = None,
    checkpoint_cb=None,
    mode: str = "auto",
    data_axis: str = "data",
    model_axis: str = "model",
    split_panels: bool | None = None,
):
    """Distributed APSP over the production mesh.

    checkpoint_cb(g, next_iter) is invoked between segments if given.
    """
    n = g.shape[0]
    pd = mesh_axis_size(mesh, data_axis)
    b = b or n // pd
    q = n // b
    segment = segment or q
    seg_fn = make_apsp_segment(
        mesh, n=n, b=b, data_axis=data_axis, model_axis=model_axis, mode=mode,
        split_panels=split_panels,
    )
    lo = 0
    while lo < q:
        hi = min(lo + segment, q)
        g = seg_fn(g, jnp.int32(lo), jnp.int32(hi))
        if checkpoint_cb is not None:
            checkpoint_cb(g, hi)
        lo = hi
    return g
