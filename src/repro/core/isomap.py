"""End-to-end Isomap (paper Alg. 1) - drivers composed from the staged
:class:`~repro.core.pipeline.ManifoldPipeline`.

    1. G = KNN(X, k)
    2. A = ALLPAIRSSHORTESTPATHS(G)
    3. D = DOUBLECENTER(A^{o2})
    4. (Q_d, Delta_d) = EIGENDECOMPOSITION(D)
    5. Y = Q_d . Delta_d^{1/2}

``isomap`` and ``isomap_distributed`` are the same stage chain over the
local and mesh backends respectively.  ``landmark_isomap`` (de Silva &
Tenenbaum; the approximate baseline the paper positions itself against)
reuses the pipeline's kNN + graph stages and swaps the O(n^3) APSP tail
for m landmark Bellman-Ford rows + landmark MDS + triangulation.  The
landmark tail itself is backend-dispatched: :func:`landmark_tail_local`
on one device, :func:`landmark_tail_sharded` (Bellman-Ford rows relaxed
against the tile-sharded graph under ``shard_map``) on a mesh.  Under the
pipeline engine the tail runs as a :class:`ResumableStage` - relaxation
sweeps are engine-owned segments, so the m x n landmark panel checkpoints
mid-sweep on big graphs exactly like APSP's diagonal panels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import spectral
from repro.kernels import ops
from repro.core.pipeline import (
    APSPStage,
    GraphStage,
    KNNStage,
    LocalBackend,
    ManifoldPipeline,
    MeshBackend,
    PipelineConfig,
    isomap_stages,
)
from repro.core.postprocess import clamp_disconnected, embedding_from_eig


@dataclasses.dataclass
class IsomapConfig:
    k: int = 10            # neighbourhood size (paper uses 10 throughout)
    d: int = 2             # target dimension
    max_iter: int = 100    # power-iteration cap (paper l=100)
    tol: float = 1e-9      # convergence threshold (paper t=1e-9)
    block: int = 512       # logical block size b
    kernel_mode: str = "auto"

    def to_pipeline(self) -> PipelineConfig:
        return PipelineConfig(
            k=self.k, d=self.d, max_iter=self.max_iter, tol=self.tol,
            block=self.block, kernel_mode=self.kernel_mode,
        )


@dataclasses.dataclass
class IsomapResult:
    embedding: jax.Array          # (n, d) = Y
    eigenvalues: jax.Array        # (d,)
    geodesics: jax.Array | None   # (n, n) A, when kept
    iterations: int


def _result_from_artifacts(art, *, keep_geodesics: bool) -> IsomapResult:
    return IsomapResult(
        embedding=art["embedding"],
        eigenvalues=art["eigenvalues"],
        geodesics=art["geodesics"] if keep_geodesics else None,
        iterations=int(art["iterations"]),
    )


def isomap(
    x: jax.Array,
    cfg: IsomapConfig,
    *,
    keep_geodesics: bool = False,
    checkpoint=None,
    resume: bool = False,
):
    """Single-device exact Isomap - the oracle the distributed path must
    match bit-for-bit in its math.

    checkpoint/resume: optional CheckpointManager making every stage
    boundary a restart point (see ManifoldPipeline).
    """
    pipe = ManifoldPipeline(
        isomap_stages(),
        backend=LocalBackend(),
        cfg=cfg.to_pipeline(),
        checkpoint=checkpoint,
    )
    art = pipe.run(x, resume=resume)
    return _result_from_artifacts(art, keep_geodesics=keep_geodesics)


def isomap_distributed(
    x: jax.Array,
    cfg: IsomapConfig,
    mesh: Mesh,
    *,
    data_axis: str = "data",
    model_axis: str = "model",
    checkpoint_cb: Callable | None = None,
    segment: int | None = None,
    checkpoint=None,
    resume: bool = False,
):
    """Distributed exact Isomap over a 2-D mesh.

    x: (n, D), sharded P(data_axis, model_axis) (rows over data, features
    over model).  Returns IsomapResult with a replicated (n, d) embedding.
    checkpoint_cb/segment checkpoint *within* the APSP stage (panel
    granularity); checkpoint/resume snapshot *between* stages.
    """
    backend = MeshBackend(
        mesh, data_axis=data_axis, model_axis=model_axis,
        segment=segment, checkpoint_cb=checkpoint_cb,
    )
    pipe = ManifoldPipeline(
        isomap_stages(),
        backend=backend,
        cfg=cfg.to_pipeline(),
        checkpoint=checkpoint,
    )
    art = pipe.run(x, resume=resume)
    return _result_from_artifacts(art, keep_geodesics=True)


# ------------------------------------------------- Landmark Isomap --------


@functools.partial(jax.jit, static_argnames=("m", "d"))
def _landmark_mds(dl: jax.Array, *, m: int, d: int):
    """Landmark MDS + triangulation on clamped (m, n) landmark geodesics.

    Replicated-size compute - O(m^2 d + n m d) - shared verbatim by the
    local and mesh landmark tails (the mesh path hands in a replicated dl).
    """
    dl2 = jnp.square(dl)
    # landmark MDS
    mu_row = jnp.mean(dl2[:, :m], axis=1, keepdims=True)
    mu_col = jnp.mean(dl2[:, :m], axis=0, keepdims=True)
    mu = jnp.mean(dl2[:, :m])
    bm = -0.5 * (dl2[:, :m] - mu_row - mu_col + mu)
    eig = spectral.power_iteration(bm, d=d, max_iter=100, tol=1e-9)
    lam = jnp.maximum(eig.eigenvalues, 1e-12)
    l_emb = embedding_from_eig(eig.eigenvectors, lam)  # (m, d)
    # triangulation of all points (de Silva & Tenenbaum distance-based)
    pinv = eig.eigenvectors / jnp.sqrt(lam)[None, :]   # (m, d)
    mean_dl2 = jnp.mean(dl2[:, :m], axis=1)            # (m,)
    y = -0.5 * (dl2 - mean_dl2[:, None]).T @ pinv      # (n, d)
    return y, l_emb


@functools.partial(jax.jit, static_argnames=("m",))
def landmark_init_local(g: jax.Array, m: int) -> jax.Array:
    """Initial landmark rows: direct edges from the first m points
    (deterministic landmark choice; callers may permute x)."""
    return g[:m, :]


@functools.partial(jax.jit, static_argnames=("mode",))
def landmark_sweep_local(
    dl: jax.Array, g: jax.Array, sweeps, *, mode: str
):
    """Run `sweeps` Bellman-Ford relaxation sweeps of the (m, n) landmark
    rows against the graph.  Each sweep extends paths by one kNN-graph
    hop batch; min-plus is exact in fp, so any segmentation of the sweep
    count produces bit-identical rows.  `sweeps` may be traced (jnp.int32)
    so one executable serves every segment length."""

    def relax(_, dl):
        # fused seeded relaxation min(DL, DL (x) G): same kernel as APSP
        # Phase 3, so no (m, n) min-plus intermediate is materialized
        # (bit-identical to minimum(dl, minplus(dl, g)) - min is exact)
        return ops.minplus_update(dl, dl, g, mode=mode)

    return jax.lax.fori_loop(0, sweeps, relax, dl)


def landmark_finalize(dl: jax.Array, *, m: int, d: int):
    """Clamp the converged landmark rows and run landmark MDS +
    triangulation (replicated O(m^2 d + n m d) compute on any backend)."""
    return _landmark_mds(clamp_disconnected(dl), m=m, d=d)


def landmark_tail_local(
    g: jax.Array, *, m: int, d: int, mode: str, sweeps: int = 32
):
    """Landmark geodesics + landmark MDS + triangulation on a built graph.

    32 sweeps covers the hop diameters of the benchmark graphs (validated
    in tests via fixed-point check).  Composed from the segment primitives
    the pipeline engine checkpoints between (init / sweep / finalize).
    """
    dl = landmark_init_local(g, m)
    dl = landmark_sweep_local(dl, g, jnp.int32(sweeps), mode=mode)
    return landmark_finalize(dl, m=m, d=d)


@functools.lru_cache(maxsize=None)
def make_landmark_init_sharded(
    mesh, n, m, *, data_axis="data", model_axis="model"
):
    """Build the jit'd shard_map extracting the initial (m, n) landmark
    rows from the tile-sharded graph, replicated on every device: each
    data shard contributes the rows it owns, a masked psum + model gather
    complete the panel."""
    from repro.sharding.logical import folded_axis_index, mesh_axis_size

    pd = mesh_axis_size(mesh, data_axis)
    pm = mesh_axis_size(mesh, model_axis)
    if n % pd or n % pm:
        raise ValueError(f"n {n} must divide the mesh axes ({pd}, {pm})")
    nr = n // pd

    def shard_fn(g_loc):
        di = folded_axis_index(data_axis)
        row_ids = jnp.arange(m)
        owner = row_ids // nr
        local = jnp.clip(row_ids - di * nr, 0, nr - 1)
        sl = jnp.where((owner == di)[:, None], g_loc[local], 0.0)  # (m, nc)
        dl_cols = jax.lax.psum(sl, data_axis)
        return jax.lax.all_gather(dl_cols, model_axis, axis=1, tiled=True)

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(data_axis, model_axis),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def make_landmark_sweep_sharded(
    mesh, n, m, mode, *, data_axis="data", model_axis="model"
):
    """Build the jit'd shard_map running Bellman-Ford relaxation sweeps
    of the replicated (m, n) landmark rows against the tile-sharded
    graph.  The sweep count is a traced argument, so the pipeline engine
    can run any segment of the sweep loop (and checkpoint dl between
    segments) through one compiled executable."""
    from repro.sharding.logical import folded_axis_index, mesh_axis_size

    pd = mesh_axis_size(mesh, data_axis)
    pm = mesh_axis_size(mesh, model_axis)
    if n % pd or n % pm:
        raise ValueError(f"n {n} must divide the mesh axes ({pd}, {pm})")
    nr = n // pd

    def shard_fn(g_loc, dl, sweeps):
        di = folded_axis_index(data_axis)

        def relax(_, dl):
            # per-device partial min over its row chunk of the contraction
            # index, completed by a pmin across the data axis; min-plus is
            # exact in fp so the sharded sweep is bit-identical to local
            dl_chunk = jax.lax.dynamic_slice_in_dim(dl, di * nr, nr, axis=1)
            part = ops.minplus(dl_chunk, g_loc, mode=mode)     # (m, nc)
            full = jax.lax.pmin(part, data_axis)
            cols = jax.lax.all_gather(full, model_axis, axis=1, tiled=True)
            return jnp.minimum(dl, cols)

        return jax.lax.fori_loop(0, sweeps, relax, dl)

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def landmark_tail_sharded(
    g: jax.Array,
    mesh: Mesh,
    *,
    m: int,
    d: int,
    mode: str = "auto",
    sweeps: int = 32,
    data_axis: str = "data",
    model_axis: str = "model",
):
    """Mesh tail: the O(m n^2) Bellman-Ford sweeps run sharded over the
    data axis (per-device work and graph residency are 1/p of local); the
    O(m^2) landmark MDS then runs replicated, same as the spectral stage's
    redundant QR - centralization would cost more than it saves."""
    n = g.shape[0]
    dl = make_landmark_init_sharded(
        mesh, n, m, data_axis=data_axis, model_axis=model_axis
    )(g)
    dl = make_landmark_sweep_sharded(
        mesh, n, m, mode, data_axis=data_axis, model_axis=model_axis
    )(g, dl, jnp.int32(sweeps))
    return landmark_finalize(dl, m=m, d=d)


class LandmarkStage:
    """Pipeline tail replacing apsp/clamp/center/eigen for L-Isomap.

    A ResumableStage: units are Bellman-Ford relaxation sweeps, state is
    the (m, n) landmark-row panel, so the m x n landmark tail can
    checkpoint mid-sweep on big graphs.  `segment_requires` keeps the
    graph in mid-sweep checkpoints - unlike APSP, every sweep relaxes
    against the original graph, so state alone cannot continue the stage.
    Dispatches through the context's backend like every other stage."""

    name = "landmark"
    requires = ("graph",)
    provides = ("embedding", "landmark_embedding")
    exports = ("embedding", "landmark_embedding")
    segment_requires = ("graph",)
    # resume identity: a checkpoint written with a different landmark
    # count or sweep budget must not be adopted (`segment` is NOT part of
    # identity - resuming with a different segmentation is elastic)
    params = ("m", "sweeps")

    def __init__(self, m: int, *, sweeps: int = 32, segment: int | None = None):
        self.m = m
        self.sweeps = sweeps
        self.segment = segment

    def num_units(self, ctx, art):
        return self.sweeps

    def init_state(self, ctx, art):
        return {"dl": ctx.backend.landmark_init(ctx.cfg, art["graph"], self.m)}

    def run_segment(self, ctx, art, state, lo, hi):
        dl = ctx.backend.landmark_sweep(
            ctx.cfg, art["graph"], state["dl"], lo, hi
        )
        return {"dl": dl}

    def finalize(self, ctx, art, state):
        y, l_emb = ctx.backend.landmark_finalize(ctx.cfg, state["dl"], self.m)
        return {"embedding": y, "landmark_embedding": l_emb}

    def run(self, ctx, art):
        """Unsegmented fallback (direct use outside the engine)."""
        state = self.init_state(ctx, art)
        state = self.run_segment(ctx, art, state, 0, self.num_units(ctx, art))
        return self.finalize(ctx, art, state)


def landmark_isomap(
    x: jax.Array,
    *,
    k: int,
    m: int,
    d: int,
    mode: str = "auto",
    mesh: Mesh | None = None,
    data_axis: str = "data",
    model_axis: str = "model",
):
    """L-Isomap baseline (paper SV): m landmarks, Bellman-Ford geodesics
    from landmarks only, landmark MDS + triangulation.  O(m n^2) instead of
    O(n^3); approximate.  Composed from the pipeline's kNN/graph stages +
    the landmark tail stage; pass `mesh` to run the same stages over the
    MeshBackend (sharded kNN + sharded landmark rows)."""
    x = jnp.asarray(x)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        backend = MeshBackend(
            mesh, data_axis=data_axis, model_axis=model_axis
        )
        x = jax.device_put(
            x, NamedSharding(mesh, PartitionSpec(data_axis, model_axis))
        )
    else:
        backend = LocalBackend()
    pipe = ManifoldPipeline(
        [KNNStage(), GraphStage(), LandmarkStage(m)],
        backend=backend,
        cfg=PipelineConfig(k=k, d=d, kernel_mode=mode),
        name="landmark_isomap",
    )
    art = pipe.run(x)
    return art["embedding"], art["landmark_embedding"]


