"""Staged execution engine for manifold-learning pipelines.

Every driver in this repo (local/distributed exact Isomap, Landmark
Isomap, LLE, the streaming new-point mapper) is a composition of the same
stage chain the paper formalizes as Alg. 1; this module makes that chain a
first-class object.  Stage -> paper mapping:

  ==========  =====================================================
  stage name  paper Alg. 1 step
  ==========  =====================================================
  ``knn``     step 1, G = KNN(X, k): exact k-nearest neighbours
  ``graph``   step 1, G assembly: kNN lists -> dense (n, n) graph
  ``apsp``    step 2, A = AllPairsShortestPaths(G) (blocked FW)
  ``clamp``   guard between steps 2/3: finite-ize +inf geodesics
  ``center``  step 3, B = DoubleCenter(A^{o2})
  ``eigen``   steps 4-5, (Q_d, Delta_d) and Y = Q_d Delta_d^{1/2}
  ==========  =====================================================

Artifact-lifecycle architecture
-------------------------------
(Stable prose reference: docs/architecture.md; the kernel layer the APSP
stage dispatches into is covered by docs/kernels.md.)

A :class:`Stage` consumes ``requires`` artifacts and produces ``provides``
artifacts, executed by :class:`ManifoldPipeline` over a
:class:`LocalBackend` or :class:`MeshBackend` (single-device and
mesh-sharded are two backends of ONE pipeline, not parallel codepaths).
Artifacts live in an :class:`~repro.core.artifacts.ArtifactStore`, which
tracks three things per artifact and is the engine's unit of memory and
fault-tolerance discipline:

* **producer + liveness** - after stage i, the live set is
  ``{"x"} | exports | union(requires of the remaining stages)``.
  ``exports`` (per-stage ``exports`` declarations, overridable per
  pipeline) name the artifacts that outlive the run - the fitted
  serving state (``geodesics``, ``embedding``, eigen outputs).
  Consumed intermediates (``graph``, ``geodesics_raw``, ``gram``,
  kNN lists) are dropped the moment their last consumer has run, so
  both peak residency and every checkpoint payload are O(n^2), not
  O(stages * n^2).
* **placement** - where the artifact lives on the backend, recorded in
  mesh *roles* ("data"/"model") rather than concrete axis names.  The
  stage-boundary checkpoints persist only the live set plus placements;
  ``run(resume=True)`` restores by ``device_put``-ing each artifact
  straight onto the *current* backend's mesh - elastic restart onto a
  different mesh shape (4x2 -> 2x4, test-proven) or from a local fit
  onto a mesh is "load + place", no resharding codepath per stage.
* **segments** - a :class:`ResumableStage` additionally exposes its
  inner loop as engine-owned segments (``num_units`` /
  ``init_state`` / ``run_segment`` / ``finalize``).  The engine runs
  the segments, checkpoints the segment state + a progress manifest
  between them (the paper's every-K-iterations lineage checkpoint),
  and on resume re-enters *mid-stage* at the recorded unit.  Both
  the blocked-Floyd-Warshall ``apsp`` stage (units = diagonal
  panels) and the landmark Bellman-Ford tail (units = relaxation
  sweeps) execute this way on both backends.

Persisted artifacts are reusable state in their own right - the streaming
mapper (:class:`repro.core.streaming.StreamingMapper`) serves new-point
queries straight from a fitted pipeline's exported ``geodesics`` +
``embedding`` artifacts (Schoeneman et al.'s stream/batch combination
point), and :mod:`repro.launch.serving` provides the batched
request/response surface in front of it.  The serving state is also
*updatable*: both backends implement the border-expansion hooks
(``expand_geodesics`` / ``place_rows`` / ``absorb_multiple``) that
:mod:`repro.core.update` uses to fold accepted stream arrivals back into
the geodesic system without a refit.

LLE registers its own tail stages (``lle_weights``, ``lle_eigen``) behind
the shared ``knn`` stage - the paper's "extends to other spectral methods
with minimal effort" claim, now expressed as stage substitution.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import apsp as apsp_mod
from repro.core import centering, graph, knn as knn_mod, spectral, telemetry
from repro.core.artifacts import (
    SEGMENT_STATE_KEY,
    ArtifactStore,
    placement_to_spec,
    spec_to_placement,
)
from repro.core.postprocess import clamp_disconnected, embedding_from_eig

Artifacts = dict[str, Any]

# Step numbering: stage-boundary checkpoints land at (i+1)*_STEP_STRIDE,
# mid-stage segment checkpoints of stage i at i*_STEP_STRIDE + unit - so
# steps sort by pipeline progress and a directory listing interleaves
# boundary and partial checkpoints correctly.
_STEP_STRIDE = 1_000_000


@dataclasses.dataclass
class PipelineConfig:
    """Stage hyperparameters (mirrors the paper's Alg. 1 knobs)."""

    k: int = 10            # neighbourhood size (paper uses 10 throughout)
    d: int = 2             # target dimension
    max_iter: int = 100    # power-iteration cap (paper l=100)
    tol: float = 1e-9      # convergence threshold (paper t=1e-9)
    block: int = 512       # logical block size b
    kernel_mode: str = "auto"
    lle_reg: float = 1e-3  # LLE local-Gram regularizer
    # scale regime: "dense" = exact (n, n) path, "sparse" = landmark panel
    # over the CSR graph (never materializes (n, n)), "auto" = dense while
    # it fits the REPRO_DENSE_BYTES budget, sparse beyond (see stages_for)
    regime: str = "auto"
    landmarks: int = 0     # sparse-regime landmark budget (0 = sqrt-rule)
    # embedding objective: "spectral" (classical MDS eigensolve),
    # "stress" (Sammon stress refined by AdamW), "path" (path-based
    # landmark Isomap) - see repro.core.embedding.OBJECTIVES
    objective: str = "spectral"


# ------------------------------------------------------------ backends ----


class LocalBackend:
    """Single-device execution of the primitive stage ops.

    segment: optional unit count per segment for ResumableStages (None =
    run each stage's inner loop in one shot); mirrors MeshBackend.
    checkpoint_secs: when `segment` is unset, derive it from this target
    checkpoint interval (seconds) using the measured time of the stage's
    first unit - the wall-clock analogue of the paper's
    every-10-iterations cadence (see ManifoldPipeline._run_resumable).
    """

    kind = "local"

    #: arrival-batch granularity for geodesic absorbs (any size works on
    #: one device)
    absorb_multiple = 1

    def __init__(
        self,
        *,
        segment: int | None = None,
        checkpoint_secs: float | None = None,
    ):
        self.segment = segment
        self.checkpoint_secs = checkpoint_secs

    def knn(self, cfg: PipelineConfig, x):
        n = x.shape[0]
        return knn_mod.knn_blocked(
            x, k=cfg.k, block=min(cfg.block, n), mode=cfg.kernel_mode
        )

    def graph(self, cfg: PipelineConfig, dists, idx, n: int):
        return graph.knn_to_graph(dists, idx, n=n)

    def clamp(self, cfg: PipelineConfig, a):
        return jax.jit(clamp_disconnected)(a)

    def center(self, cfg: PipelineConfig, a):
        return centering.double_center(jnp.square(a))

    def eigen(self, cfg: PipelineConfig, b):
        return spectral.power_iteration(
            b, d=cfg.d, max_iter=cfg.max_iter, tol=cfg.tol
        )

    # --- segmented APSP (ResumableStage hooks) ---

    def apsp_num_units(self, cfg: PipelineConfig, n: int) -> int:
        return n // min(cfg.block, n)

    def apsp_segment(self, cfg: PipelineConfig, g, lo: int, hi: int):
        n = g.shape[0]
        return apsp_mod.apsp_blocked_segment(
            g, jnp.int32(lo), jnp.int32(hi),
            block=min(cfg.block, n), mode=cfg.kernel_mode,
        )

    # --- segmented landmark Bellman-Ford tail ---

    def landmark_init(self, cfg: PipelineConfig, g, m: int):
        from repro.core.isomap import landmark_init_local

        return landmark_init_local(g, m)

    def landmark_sweep(self, cfg: PipelineConfig, g, dl, lo: int, hi: int):
        from repro.core.isomap import landmark_sweep_local

        return landmark_sweep_local(
            dl, g, jnp.int32(hi - lo), mode=cfg.kernel_mode
        )

    def landmark_finalize(self, cfg: PipelineConfig, dl, m: int):
        from repro.core.isomap import landmark_finalize as _fin

        return _fin(dl, m=m, d=cfg.d)

    # --- streaming tail ---

    def row_mean_sq(self, geodesics):
        from repro.core.streaming import geodesic_row_mean_sq

        return geodesic_row_mean_sq(geodesics)

    def map_new_points(
        self, x_new, x_base, geodesics, embedding, *, k: int, mean_sq=None
    ):
        from repro.core.streaming import map_new_points

        return map_new_points(
            x_new, x_base, geodesics, embedding, k=k, mean_sq=mean_sq
        )

    def new_point_geodesics(self, x_new, x_base, geodesics, *, k: int):
        """(b, n) geodesic rows for out-of-sample points (no embedding)."""
        from repro.core.streaming import new_point_geodesics

        return new_point_geodesics(x_new, x_base, geodesics, k=k)

    def gather_rows(self, a, idx):
        """Gather rows of a backend-placed matrix onto a dense array."""
        return jnp.asarray(a)[jnp.asarray(idx)]

    # --- updatable-manifold tail ---

    def expand_geodesics(self, a, e, f, *, mode: str = "auto"):
        from repro.core.update import expand_geodesics

        return expand_geodesics(a, e, f, mode=mode)

    def place_rows(self, x):
        """Place a (n, D) point set the way this backend serves it."""
        return jnp.asarray(x)

    # --- sparse scale regime (landmark panel over the CSR graph) ---

    #: landmark counts need no divisibility on one device
    landmark_multiple = 1

    def csr_graph(self, cfg: PipelineConfig, dists, idx, n: int):
        return graph.knn_to_padded_csr(dists, idx, n=n)

    def place_replicated(self, value):
        return jnp.asarray(value)

    def sparse_num_units(self, cfg: PipelineConfig, m: int, csr_shape):
        from repro.core import sparse as sparse_mod
        from repro.kernels import autotune

        n, deg = csr_shape
        fcfg = autotune.frontier_config(n, deg, m)
        return sparse_mod.sparse_units(m, min(fcfg.bs, m))

    def sparse_init(self, cfg: PipelineConfig, m: int, n: int):
        return jnp.full((m, n), jnp.inf, dtype=jnp.float32)

    def sparse_segment(
        self, cfg: PipelineConfig, nbr, w, lm_idx, panel, lo: int, hi: int
    ):
        from repro.core import sparse as sparse_mod
        from repro.kernels import autotune

        n, deg = nbr.shape
        m = lm_idx.shape[0]
        fcfg = autotune.frontier_config(n, deg, m)
        delta = sparse_mod.frontier_delta(w, fcfg.bucket)
        return sparse_mod.sparse_panel_segment(
            nbr, w, lm_idx, panel, jnp.int32(lo), jnp.int32(hi), delta,
            bs=min(fcfg.bs, m), bucket=fcfg.bucket, bn=fcfg.bn,
            mode=cfg.kernel_mode,
        )

    def sparse_embed(self, cfg: PipelineConfig, panel, lm_idx):
        from repro.core import sparse as sparse_mod

        return sparse_mod.landmark_mds_general(
            panel, lm_idx, d=cfg.d, max_iter=cfg.max_iter, tol=cfg.tol
        )

    # --- artifact placement (trivial on one device) ---

    def placement_of(self, value):
        return None

    def place(self, value, placement):
        return jnp.asarray(value)


@functools.lru_cache(maxsize=None)
def _make_tiled_graph(tile_spec, n: int):
    """The dense kNN graph, built straight into the mesh's tiles (one
    executable per mesh and n)."""
    return jax.jit(
        functools.partial(graph.knn_to_graph, n=n), out_shardings=tile_spec
    )


@functools.lru_cache(maxsize=None)
def _make_gather_rows(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(
        lambda a, i: jnp.take(a, i, axis=0),
        out_shardings=NamedSharding(mesh, P()),
    )


class MeshBackend:
    """Mesh-sharded execution: same stage chain, explicit collectives.

    segment sizes the engine-owned intra-stage checkpoints of
    ResumableStages (APSP panels, landmark sweeps - the paper's
    every-K-iterations lineage checkpoint); checkpoint_cb is the legacy
    per-APSP-segment hook (called with the evolving sharded matrix).
    The *inter-stage* resume points are owned by :class:`ManifoldPipeline`.
    """

    kind = "sharded"

    def __init__(
        self,
        mesh,
        *,
        data_axis: str = "data",
        model_axis: str = "model",
        segment: int | None = None,
        checkpoint_secs: float | None = None,
        checkpoint_cb: Callable | None = None,
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.segment = segment
        self.checkpoint_secs = checkpoint_secs
        self.checkpoint_cb = checkpoint_cb
        self.tile_spec = NamedSharding(mesh, P(data_axis, model_axis))

    def knn(self, cfg: PipelineConfig, x):
        pd = self.mesh.shape[self.data_axis]
        pm = self.mesh.shape[self.model_axis]
        return knn_mod.knn_ring(
            x, k=cfg.k, mesh=self.mesh,
            row_axis=self.data_axis, feat_axis=self.model_axis,
            split_axis=self.model_axis if pd % pm == 0 else None,
            mode=cfg.kernel_mode,
        )

    def graph(self, cfg: PipelineConfig, dists, idx, n: int):
        return _make_tiled_graph(self.tile_spec, n)(dists, idx)

    def clamp(self, cfg: PipelineConfig, a):
        return jax.jit(clamp_disconnected, out_shardings=self.tile_spec)(a)

    def center(self, cfg: PipelineConfig, a):
        sq = jax.jit(jnp.square, out_shardings=self.tile_spec)(a)
        return centering.double_center_sharded(
            sq, self.mesh,
            data_axis=self.data_axis, model_axis=self.model_axis,
        )

    def eigen(self, cfg: PipelineConfig, b):
        n = b.shape[0]
        eig_fn = spectral.make_power_iteration_sharded(
            self.mesh, n=n, d=cfg.d, max_iter=cfg.max_iter, tol=cfg.tol,
            data_axis=self.data_axis, model_axis=self.model_axis,
        )
        return eig_fn(b)

    # --- segmented APSP (ResumableStage hooks) ---

    def apsp_num_units(self, cfg: PipelineConfig, n: int) -> int:
        # clamp like LocalBackend: block > n must not yield 0 units (the
        # engine would silently skip APSP); make_apsp_segment still
        # asserts the block fits the local tile
        return n // min(cfg.block, n)

    def apsp_segment(self, cfg: PipelineConfig, g, lo: int, hi: int):
        n = g.shape[0]
        seg_fn = apsp_mod.cached_apsp_segment(
            self.mesh, n=n, b=min(cfg.block, n),
            data_axis=self.data_axis, model_axis=self.model_axis,
            mode=cfg.kernel_mode,
        )
        return seg_fn(g, jnp.int32(lo), jnp.int32(hi))

    # --- segmented landmark Bellman-Ford tail ---

    def landmark_init(self, cfg: PipelineConfig, g, m: int):
        from repro.core.isomap import make_landmark_init_sharded

        fn = make_landmark_init_sharded(
            self.mesh, g.shape[0], m,
            data_axis=self.data_axis, model_axis=self.model_axis,
        )
        return fn(g)

    def landmark_sweep(self, cfg: PipelineConfig, g, dl, lo: int, hi: int):
        from repro.core.isomap import make_landmark_sweep_sharded

        fn = make_landmark_sweep_sharded(
            self.mesh, g.shape[0], dl.shape[0], cfg.kernel_mode,
            data_axis=self.data_axis, model_axis=self.model_axis,
        )
        return fn(g, dl, jnp.int32(hi - lo))

    def landmark_finalize(self, cfg: PipelineConfig, dl, m: int):
        from repro.core.isomap import landmark_finalize as _fin

        return _fin(dl, m=m, d=cfg.d)

    # --- streaming tail ---

    def row_mean_sq(self, geodesics):
        from repro.core.streaming import _make_row_mean_sq_sharded

        return _make_row_mean_sq_sharded(
            self.mesh, geodesics.shape[0], self.data_axis, self.model_axis
        )(geodesics)

    def map_new_points(
        self, x_new, x_base, geodesics, embedding, *, k: int, mean_sq=None
    ):
        from repro.core.streaming import map_new_points_sharded

        return map_new_points_sharded(
            x_new, x_base, geodesics, embedding, self.mesh, k=k,
            data_axis=self.data_axis, model_axis=self.model_axis,
            mean_sq=mean_sq,
        )

    def new_point_geodesics(self, x_new, x_base, geodesics, *, k: int):
        from repro.core.streaming import new_point_geodesics_sharded

        return new_point_geodesics_sharded(
            x_new, x_base, geodesics, self.mesh, k=k,
            data_axis=self.data_axis, model_axis=self.model_axis,
        )

    def gather_rows(self, a, idx):
        """Gather rows of a tile-sharded matrix, replicated on out - the
        handful of path/landmark rows an objective pulls is O(p * n),
        nowhere near the sharded budget."""
        fn = _make_gather_rows(self.mesh)
        return fn(jnp.asarray(a), jnp.asarray(idx))

    # --- updatable-manifold tail ---

    @property
    def absorb_multiple(self) -> int:
        """Arrival-batch granularity for geodesic absorbs: the grown
        matrix must keep dividing both mesh axes, so flush groups come in
        multiples of their lcm."""
        import math

        return math.lcm(
            self.mesh.shape[self.data_axis],
            self.mesh.shape[self.model_axis],
        )

    def expand_geodesics(self, a, e, f, *, mode: str = "auto"):
        """Mesh border expansion: the five fused steps run as a
        shard_map against the tile-sharded base matrix, then the grown
        (n+m, n+m) matrix is resharded across the mesh (the row/column
        chunk boundaries all move, so this is a real reshard, done once
        per flush)."""
        from repro.core.update import make_expand_sharded

        n, m = a.shape[0], e.shape[0]
        pd = self.mesh.shape[self.data_axis]
        pm = self.mesh.shape[self.model_axis]
        if (n + m) % pd or (n + m) % pm:
            raise ValueError(
                f"grown size {n + m} must divide the mesh axes "
                f"({pd}, {pm}); absorb in multiples of {self.absorb_multiple}"
            )
        fn = make_expand_sharded(
            self.mesh, n, m,
            data_axis=self.data_axis, model_axis=self.model_axis, mode=mode,
        )
        a_int, border, new_block = fn(a, jnp.asarray(e), jnp.asarray(f))
        top = jnp.concatenate([a_int, border.T], axis=1)
        bot = jnp.concatenate([border, new_block], axis=1)
        return jax.device_put(
            jnp.concatenate([top, bot], axis=0), self.tile_spec
        )

    def place_rows(self, x):
        from jax.sharding import NamedSharding, PartitionSpec as P

        if x.shape[0] % self.mesh.shape[self.data_axis]:
            raise ValueError(
                f"{x.shape[0]} rows must divide the data axis "
                f"({self.mesh.shape[self.data_axis]})"
            )
        return jax.device_put(
            jnp.asarray(x), NamedSharding(self.mesh, P(self.data_axis))
        )

    # --- sparse scale regime (landmark-batch sharding) ---

    @property
    def landmark_multiple(self) -> int:
        """Landmark rows shard over the *folded* (data, model) axis —
        every device, not every data row, owns an equal slice — so the
        count must divide the device product."""
        from repro.sharding.logical import mesh_axis_size

        return mesh_axis_size(self.mesh, (self.data_axis, self.model_axis))

    def csr_graph(self, cfg: PipelineConfig, dists, idx, n: int):
        from jax.sharding import NamedSharding, PartitionSpec as P

        nbr, w = graph.knn_to_padded_csr(dists, idx, n=n)
        rep = NamedSharding(self.mesh, P())
        return jax.device_put(nbr, rep), jax.device_put(w, rep)

    def place_replicated(self, value):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            jnp.asarray(value), NamedSharding(self.mesh, P())
        )

    def _sparse_cfg(self, m: int, n: int, deg: int):
        from repro.kernels import autotune

        ml = m // self.landmark_multiple
        fcfg = autotune.frontier_config(n, deg, ml)
        return ml, fcfg

    def sparse_num_units(self, cfg: PipelineConfig, m: int, csr_shape):
        from repro.core import sparse as sparse_mod

        n, deg = csr_shape
        ml, fcfg = self._sparse_cfg(m, n, deg)
        return sparse_mod.sparse_units(ml, min(fcfg.bs, ml))

    def sparse_init(self, cfg: PipelineConfig, m: int, n: int):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            jnp.full((m, n), jnp.inf, dtype=jnp.float32),
            NamedSharding(
                self.mesh, P((self.data_axis, self.model_axis), None)
            ),
        )

    def sparse_segment(
        self, cfg: PipelineConfig, nbr, w, lm_idx, panel, lo: int, hi: int
    ):
        from repro.core import sparse as sparse_mod

        n, deg = nbr.shape
        m = lm_idx.shape[0]
        ml, fcfg = self._sparse_cfg(m, n, deg)
        fn = sparse_mod.make_sparse_segment_sharded(
            self.mesh, m, n, deg, cfg.kernel_mode,
            bs=min(fcfg.bs, ml), bucket=fcfg.bucket, bn=fcfg.bn,
            data_axis=self.data_axis, model_axis=self.model_axis,
        )
        delta = sparse_mod.frontier_delta(w, fcfg.bucket)
        return fn(nbr, w, lm_idx, panel, jnp.int32(lo), jnp.int32(hi), delta)

    def sparse_embed(self, cfg: PipelineConfig, panel, lm_idx):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import sparse as sparse_mod

        # one replicating gather of the (m, n) panel — within the
        # O(m n) residency bound; the MDS itself is O(m^2 + n m d)
        # replicated compute, same policy as the dense landmark tail
        panel_rep = jax.device_put(panel, NamedSharding(self.mesh, P()))
        return sparse_mod.landmark_mds_general(
            panel_rep, lm_idx, d=cfg.d, max_iter=cfg.max_iter, tol=cfg.tol
        )

    # --- artifact placement (the elastic-restart hooks) ---

    def placement_of(self, value):
        """Record the artifact's partition spec in mesh roles, or None
        for host / single-device / unspecced values."""
        sharding = getattr(value, "sharding", None)
        if sharding is None:
            return None
        return spec_to_placement(sharding, self.data_axis, self.model_axis)

    def place(self, value, placement):
        """device_put a restored host array onto THIS mesh according to
        its recorded placement - the mesh it was saved from may have had
        a different shape (or axis names) entirely."""
        from jax.sharding import NamedSharding

        if placement is None:
            return jnp.asarray(value)
        spec = placement_to_spec(placement, self.data_axis, self.model_axis)
        return jax.device_put(value, NamedSharding(self.mesh, spec))


# -------------------------------------------------------------- stages ----


@runtime_checkable
class Stage(Protocol):
    """One named unit of the pipeline: consumes `requires` artifacts,
    produces `provides` artifacts.  Implementations dispatch through the
    context's backend so the same stage object runs locally or sharded.

    Optional class attributes understood by the engine:

    * ``exports`` - the subset of `provides` that outlives the run (kept
      live, persisted at every later boundary) even once all downstream
      consumers have run.
    * ``params`` - names of constructor attributes that are part of the
      stage's *identity* for resume compatibility (e.g. LandmarkStage's
      ``m``/``sweeps``): a checkpoint written with different values must
      not be adopted, exactly like a PipelineConfig mismatch.
    """

    name: str
    requires: tuple[str, ...]
    provides: tuple[str, ...]

    def run(self, ctx: "PipelineContext", art: Artifacts) -> Artifacts: ...


@runtime_checkable
class ResumableStage(Protocol):
    """A stage whose inner loop is exposed as engine-owned segments.

    The engine calls ``init_state`` once, then ``run_segment`` over unit
    ranges [lo, hi), checkpointing the returned state dict (plus a
    progress manifest: stage, unit reached, total units) between
    segments; ``finalize`` turns the final state into the stage's
    `provides`.  ``segment_requires`` names the artifacts ``run_segment``
    still reads every segment - only those (not the full `requires`) are
    persisted with mid-stage checkpoints, so a stage whose state subsumes
    its input (APSP: the evolving matrix) checkpoints one O(n^2) array,
    not two.
    """

    name: str
    requires: tuple[str, ...]
    provides: tuple[str, ...]
    segment_requires: tuple[str, ...]

    def num_units(self, ctx: "PipelineContext", art: Artifacts) -> int: ...

    def init_state(
        self, ctx: "PipelineContext", art: Artifacts
    ) -> dict[str, Any]: ...

    def run_segment(
        self, ctx: "PipelineContext", art: Artifacts,
        state: dict[str, Any], lo: int, hi: int,
    ) -> dict[str, Any]: ...

    def finalize(
        self, ctx: "PipelineContext", art: Artifacts, state: dict[str, Any]
    ) -> Artifacts: ...


def _is_resumable(stage) -> bool:
    return callable(getattr(stage, "run_segment", None))


def _stage_fingerprint(stage) -> dict:
    """Identity-relevant stage attributes (declared via ``params``) for
    resume compatibility, JSON-safe."""
    return {p: getattr(stage, p) for p in getattr(stage, "params", ())}


@dataclasses.dataclass
class PipelineContext:
    cfg: PipelineConfig
    backend: LocalBackend | MeshBackend


class KNNStage:
    name = "knn"
    requires = ("x",)
    provides = ("knn_dists", "knn_idx")

    def run(self, ctx, art):
        d, i = ctx.backend.knn(ctx.cfg, art["x"])
        return {"knn_dists": d, "knn_idx": i}


class GraphStage:
    name = "graph"
    requires = ("x", "knn_dists", "knn_idx")
    provides = ("graph",)

    def run(self, ctx, art):
        from repro.core.sparse import check_dense_budget

        n = art["x"].shape[0]
        # refuse before allocating anything O(n^2): beyond the byte
        # budget the dense regime cannot hold its three (n, n) arrays
        check_dense_budget(n)
        g = ctx.backend.graph(
            ctx.cfg, art["knn_dists"], art["knn_idx"], n=n
        )
        return {"graph": g}


class APSPStage:
    """Blocked Floyd-Warshall as a ResumableStage: units are diagonal
    panels, state is the evolving distance matrix (which subsumes the
    input graph - min-plus updates only ever tighten it), so mid-stage
    checkpoints persist exactly one O(n^2) array."""

    name = "apsp"
    requires = ("graph",)
    provides = ("geodesics_raw",)
    segment_requires = ()

    def num_units(self, ctx, art):
        # derived from x, not the graph: a mid-stage resume has already
        # dropped the graph (the evolving state subsumes it)
        return ctx.backend.apsp_num_units(ctx.cfg, art["x"].shape[0])

    def init_state(self, ctx, art):
        return {"g": art["graph"]}

    def run_segment(self, ctx, art, state, lo, hi):
        g = ctx.backend.apsp_segment(ctx.cfg, state["g"], lo, hi)
        cb = getattr(ctx.backend, "checkpoint_cb", None)
        if cb is not None:
            cb(g, hi)
        return {"g": g}

    def finalize(self, ctx, art, state):
        return {"geodesics_raw": state["g"]}

    def run(self, ctx, art):
        """Unsegmented fallback (direct use outside the engine)."""
        state = self.init_state(ctx, art)
        total = self.num_units(ctx, art)
        state = self.run_segment(ctx, art, state, 0, total)
        return self.finalize(ctx, art, state)


class ClampStage:
    name = "clamp"
    requires = ("geodesics_raw",)
    provides = ("geodesics",)
    # geodesics are serving state (StreamingMapper reattaches to them),
    # so they outlive their last in-pipeline consumer (center)
    exports = ("geodesics",)

    def run(self, ctx, art):
        return {"geodesics": ctx.backend.clamp(ctx.cfg, art["geodesics_raw"])}


class CenterStage:
    name = "center"
    requires = ("geodesics",)
    provides = ("gram",)

    def run(self, ctx, art):
        return {"gram": ctx.backend.center(ctx.cfg, art["geodesics"])}


class EigenStage:
    name = "eigen"
    requires = ("gram",)
    provides = (
        "eigenvectors", "eigenvalues", "iterations", "delta", "embedding",
    )
    exports = ("embedding", "eigenvalues", "iterations")

    def run(self, ctx, art):
        eig = ctx.backend.eigen(ctx.cfg, art["gram"])
        y = embedding_from_eig(eig.eigenvectors, eig.eigenvalues)
        return {
            "eigenvectors": eig.eigenvectors,
            "eigenvalues": eig.eigenvalues,
            "iterations": eig.iterations,
            "delta": eig.delta,
            "embedding": y,
        }


# LLE tail stages (registered behind the shared KNN stage) ------------------


class LLEWeightsStage:
    """Local reconstruction weights + dense M = (I-W)^T (I-W)."""

    name = "lle_weights"
    requires = ("x", "knn_dists", "knn_idx")
    provides = ("lle_m",)

    def run(self, ctx, art):
        from repro.core.lle import lle_embedding_matrix

        m = lle_embedding_matrix(
            art["x"], art["knn_idx"], reg=ctx.cfg.lle_reg
        )
        return {"lle_m": m}


class LLEEigenStage:
    """Bottom-spectrum extraction by simultaneous inverse iteration."""

    name = "lle_eigen"
    requires = ("lle_m",)
    provides = ("embedding",)
    exports = ("embedding",)

    def run(self, ctx, art):
        from repro.core.lle import lle_bottom_eigen

        return {"embedding": lle_bottom_eigen(art["lle_m"], d=ctx.cfg.d)}


def isomap_stages(objective=None) -> list[Stage]:
    """The Alg. 1 chain; the embedding tail comes from the objective
    (default SpectralMDS, i.e. the historical center+eigen stages)."""
    from repro.core.embedding import get_objective

    return [
        KNNStage(), GraphStage(), APSPStage(), ClampStage(),
        *get_objective(objective).dense_stages(),
    ]


def lle_stages(objective=None) -> list[Stage]:
    """LLE = shared kNN front + objective-declared LLE tail."""
    from repro.core.embedding import get_objective

    return [KNNStage(), *get_objective(objective).lle_tail_stages()]


def stages_for(cfg: PipelineConfig, n: int) -> list[Stage]:
    """Scale-regime selection: the stage chain for an n-point fit.

    ``cfg.regime``: "dense" pins the exact (n, n) chain (the oracle —
    still refused by GraphStage past the byte budget), "sparse" pins the
    landmark-panel chain, "auto" picks dense exactly while its three
    (n, n) arrays fit ``REPRO_DENSE_BYTES`` and sparse beyond — so small
    fits keep bit-exact geodesics and big fits keep O(n k + m n)
    residency, with no flag day in between.  ``cfg.objective`` selects
    the embedding tail in either regime."""
    from repro.core import sparse as sparse_mod
    from repro.core.embedding import get_objective

    objective = get_objective(getattr(cfg, "objective", None))
    regime = getattr(cfg, "regime", "auto")
    if regime == "dense":
        return isomap_stages(objective)
    if regime == "sparse":
        return sparse_mod.sparse_isomap_stages(
            cfg.landmarks or None, objective
        )
    if regime == "auto":
        if sparse_mod.dense_budget_ok(n):
            return isomap_stages(objective)
        return sparse_mod.sparse_isomap_stages(
            cfg.landmarks or None, objective
        )
    raise ValueError(
        f"unknown regime {regime!r} (expected dense/sparse/auto)"
    )


# ------------------------------------------------------------ pipeline ----


def _same_input(x_saved, x) -> bool:
    """Value check for resume: a same-shape but different dataset must not
    silently adopt the checkpointed artifacts (shape alone can't tell a
    seed-0 fit from a seed-1 run).  Compared in the saved dtype so passing
    the original points at a wider dtype still resumes."""
    import numpy as np

    x_saved = np.asarray(x_saved)
    return bool(np.array_equal(x_saved, np.asarray(x, dtype=x_saved.dtype)))


@dataclasses.dataclass
class _ResumePoint:
    """What the resume scan found: the first stage index to (re-)enter,
    the restored host artifacts + their lifecycle metadata, and - for a
    mid-stage re-entry - the segment state and the unit to continue at."""

    start: int
    artifacts: dict | None = None
    placements: dict = dataclasses.field(default_factory=dict)
    producers: dict = dataclasses.field(default_factory=dict)
    seg_state: dict | None = None
    seg_lo: int = 0


class ManifoldPipeline:
    """Executes a stage list over one backend with artifact-lifecycle
    management: liveness pruning, placement-aware elastic checkpoints,
    and segment-level (mid-stage) resume for ResumableStages.

    checkpoint: optional :class:`repro.checkpoint.CheckpointManager`.
    After stage i completes, the *live* artifact set (see module
    docstring) is saved at step (i+1)*stride with the stage name, config
    fingerprint, per-artifact producers and placements in the manifest;
    between segments of a ResumableStage the segment state is saved with
    a progress manifest.  ``run(..., resume=True)`` restores the newest
    compatible checkpoint - boundary or mid-stage - places every artifact
    onto the current backend (elastic restart), and re-executes only the
    remaining work.
    checkpoint_artifacts: additionally restrict which artifacts are
    persisted (applied on top of liveness; "x" is always kept); None
    saves the full live set.
    exports: artifacts that must survive to the end of the run (and
    hence into every later checkpoint).  Default: "x", every stage's
    declared ``exports``, and the final stage's `provides`.
    """

    def __init__(
        self,
        stages: Sequence[Stage] | None = None,
        *,
        backend: LocalBackend | MeshBackend | None = None,
        cfg: PipelineConfig | None = None,
        checkpoint=None,
        checkpoint_artifacts: Sequence[str] | None = None,
        exports: Sequence[str] | None = None,
        name: str = "isomap",
    ):
        self.stages = list(stages) if stages is not None else isomap_stages()
        self.ctx = PipelineContext(
            cfg=cfg or PipelineConfig(), backend=backend or LocalBackend()
        )
        self.checkpoint = checkpoint
        self.checkpoint_artifacts = (
            tuple(checkpoint_artifacts)
            if checkpoint_artifacts is not None
            else None
        )
        self.name = name
        self._validate()
        if exports is not None:
            self.exports = tuple(dict.fromkeys(["x", *exports]))
        else:
            ex = {"x"}
            for s in self.stages:
                ex |= set(getattr(s, "exports", ()))
            ex |= set(self.stages[-1].provides)
            self.exports = tuple(sorted(ex))
        producible = {"x"}
        for s in self.stages:
            producible |= set(s.provides)
        unknown = set(self.exports) - producible
        if unknown:
            raise ValueError(
                f"exports {sorted(unknown)} are not produced by any stage "
                f"(producible: {sorted(producible)})"
            )

    @property
    def cfg(self) -> PipelineConfig:
        return self.ctx.cfg

    @property
    def backend(self):
        return self.ctx.backend

    def _validate(self):
        names = [s.name for s in self.stages]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate stage names: {names}")
        available = {"x"}
        for s in self.stages:
            missing = set(s.requires) - available
            if missing:
                raise ValueError(
                    f"stage {s.name!r} requires {sorted(missing)} but only "
                    f"{sorted(available)} are produced upstream"
                )
            available.update(s.provides)

    # --------------------------------------------------------- liveness --

    def _live_after(self, i: int) -> set[str]:
        """Artifacts that must stay resident once stage i has completed:
        the exports plus everything any remaining stage still consumes."""
        live = {"x"} | set(self.exports)
        for s in self.stages[i + 1:]:
            live |= set(s.requires)
            live |= set(getattr(s, "segment_requires", ()))
        return live

    def _live_during(self, i: int) -> set[str]:
        """Artifacts a *mid-stage* checkpoint of stage i must persist:
        what stage i's remaining segments read, plus everything after."""
        stage = self.stages[i]
        return self._live_after(i) | set(
            getattr(stage, "segment_requires", ())
        )

    # ----------------------------------------------------------- resume --

    def _cfg_fingerprint(self) -> dict:
        """JSON-round-tripped config dict, comparable against manifests."""
        import json

        return json.loads(json.dumps(dataclasses.asdict(self.ctx.cfg)))

    def _stage_params_fingerprint(self) -> dict:
        """{stage name: identity params} for every stage declaring any,
        JSON-round-tripped for manifest comparison."""
        import json

        fps = {
            s.name: _stage_fingerprint(s)
            for s in self.stages
            if _stage_fingerprint(s)
        }
        return json.loads(json.dumps(fps))

    def _find_resume_point(self) -> _ResumePoint:
        """Scan checkpoints newest-first for a usable re-entry point.

        A checkpoint is only a valid resume point if (a) it was written by
        a pipeline with this name AND the same config (a k=10 geodesic
        matrix must not silently answer a k=15 run), and (b) its saved
        artifacts satisfy the `requires` chain of every remaining stage
        (liveness pruning / checkpoint_artifacts filtering may have
        dropped some) - otherwise the scan falls back to an older step.
        Mid-stage (partial) checkpoints additionally need their segment
        state and the stage's `segment_requires` present, and re-enter
        the stage at the recorded unit.
        """
        names = [s.name for s in self.stages]
        cfg_fp = self._cfg_fingerprint()
        state_prefix = SEGMENT_STATE_KEY + "/"
        for step in reversed(self.checkpoint.all_steps()):
            try:
                manifest = self.checkpoint.read_manifest(step)
            except (OSError, ValueError):
                continue
            if manifest.get("pipeline") != self.name:
                continue
            stage_name = manifest.get("stage")
            if stage_name not in names:
                continue
            saved_cfg = manifest.get("config")
            if saved_cfg is not None and saved_cfg != cfg_fp:
                continue
            idx = names.index(stage_name)
            # stage-identity params (e.g. LandmarkStage m/sweeps) of every
            # stage whose outputs/state this checkpoint would hand us must
            # match - a 32-landmark dl panel is not a 16-landmark answer
            saved_sp = manifest.get("stage_params") or {}
            sp_fp = self._stage_params_fingerprint()
            if any(
                saved_sp.get(s.name) != sp_fp.get(s.name)
                for s in self.stages[: idx + 1]
            ):
                continue
            keys = set(manifest.get("keys", []))
            state_keys = {k for k in keys if k.startswith(state_prefix)}
            partial = bool(manifest.get("partial"))
            if partial:
                stage = self.stages[idx]
                if not _is_resumable(stage) or not state_keys:
                    continue
                seg_req = set(getattr(stage, "segment_requires", ()))
                if not seg_req <= (keys | {"x"}):
                    continue
                start = idx
                # once stage idx finishes its remaining segments it will
                # provide its outputs; check the chain from there
                available = (keys - state_keys) | {"x"} | set(stage.provides)
                check_from = idx + 1
            else:
                start = idx + 1
                available = keys | {"x"}
                check_from = start
            satisfiable = True
            for s in self.stages[check_from:]:
                if not set(s.requires) <= available:
                    satisfiable = False
                    break
                available |= set(s.provides)
            if not satisfiable:
                continue
            try:
                restored = self.checkpoint.restore_flat(step)
            except (OSError, KeyError, ValueError):
                # step GC'd between the manifest read and the array load
                # (async writer retention), or arrays missing: fall back
                continue
            placements = manifest.get("placements") or {}
            producers = manifest.get("producers") or {}
            seg_state = None
            seg_lo = 0
            if partial:
                seg_state = {
                    k[len(state_prefix):]: v
                    for k, v in restored.items()
                    if k.startswith(state_prefix)
                }
                restored = {
                    k: v for k, v in restored.items()
                    if not k.startswith(state_prefix)
                }
                seg_lo = int(manifest.get("segment", 0))
            return _ResumePoint(
                start=start, artifacts=restored, placements=placements,
                producers=producers, seg_state=seg_state, seg_lo=seg_lo,
            )
        return _ResumePoint(start=0)

    # ------------------------------------------------------ checkpoints --

    def _checkpoint_filter(self, payload: dict) -> dict:
        if self.checkpoint_artifacts is None:
            return payload
        keep = set(self.checkpoint_artifacts) | {"x"}
        return {k: v for k, v in payload.items() if k in keep}

    def _save_boundary(self, i: int, stage, store: ArtifactStore):
        payload = self._checkpoint_filter(dict(store))
        placements = {
            k: store.record(k).placement for k in payload
        }
        self.checkpoint.save(
            (i + 1) * _STEP_STRIDE,
            payload,
            manifest_extra={
                "pipeline": self.name,
                "stage": stage.name,
                "config": self._cfg_fingerprint(),
                "stage_params": self._stage_params_fingerprint(),
                "producers": {
                    k: store.record(k).producer for k in payload
                },
                "placements": placements,
                "exports": list(self.exports),
            },
        )

    def _save_partial(
        self, i: int, stage, store: ArtifactStore,
        state: dict, hi: int, total: int,
    ):
        backend = self.ctx.backend
        live = self._live_during(i)
        payload = self._checkpoint_filter(
            {k: v for k, v in store.items() if k in live}
        )
        placements = {k: store.record(k).placement for k in payload}
        for k, v in state.items():
            placements[f"{SEGMENT_STATE_KEY}/{k}"] = backend.placement_of(v)
        payload = dict(payload)
        payload[SEGMENT_STATE_KEY] = dict(state)
        self.checkpoint.save(
            i * _STEP_STRIDE + hi,
            payload,
            manifest_extra={
                "pipeline": self.name,
                "stage": stage.name,
                "config": self._cfg_fingerprint(),
                "stage_params": self._stage_params_fingerprint(),
                "partial": True,
                "segment": hi,
                "total": total,
                "producers": {
                    k: store.record(k).producer for k in payload
                    if k != SEGMENT_STATE_KEY
                },
                "placements": placements,
                "exports": list(self.exports),
            },
        )

    # -------------------------------------------------------------- run --

    def _run_resumable(
        self, i: int, stage, store: ArtifactStore,
        seg_state: dict | None, seg_lo: int,
    ) -> Artifacts:
        """Drive a ResumableStage segment by segment, checkpointing the
        segment state + progress manifest between segments.

        Segment sizing: an explicit unit count (stage or backend
        ``segment``) wins; otherwise, when the backend sets
        ``checkpoint_secs``, the engine runs the first unit alone,
        measures it, and sizes every following segment to hit that
        wall-clock checkpoint cadence (the paper checkpoints its RDD
        lineage every 10 iterations - a fixed count tuned to its
        cluster; a seconds target adapts the count to the measured
        per-unit time of *this* problem and backend).  With neither
        knob the whole inner loop runs in one shot.
        """
        ctx = self.ctx
        total = int(stage.num_units(ctx, store))
        if total >= _STEP_STRIDE:
            raise ValueError(
                f"stage {stage.name!r} has {total} units; the step "
                f"numbering supports < {_STEP_STRIDE}"
            )
        if seg_state is None:
            state = stage.init_state(ctx, store)
            lo = 0
        else:
            state = seg_state
            lo = seg_lo
        seglen = (
            getattr(stage, "segment", None)
            or getattr(ctx.backend, "segment", None)
        )
        ckpt_secs = getattr(ctx.backend, "checkpoint_secs", None)
        if seglen is None and ckpt_secs and lo < total:
            # warm unit: the stage's first run_segment pays the one-time
            # jit compile, which would inflate the per-unit estimate by
            # orders of magnitude - run it untimed first
            state = stage.run_segment(ctx, store, state, lo, lo + 1)
            jax.block_until_ready(state)
            lo += 1
            if lo < total:
                # calibration unit: the same compiled executable serves
                # every [lo, hi) (traced bounds), so this times pure work
                t0 = time.perf_counter()
                state = stage.run_segment(ctx, store, state, lo, lo + 1)
                jax.block_until_ready(state)
                per_unit = max(time.perf_counter() - t0, 1e-9)
                seglen = max(1, int(round(ckpt_secs / per_unit)))
                lo += 1
            if self.checkpoint is not None and lo < total:
                with telemetry.span("checkpoint"):
                    self._save_partial(i, stage, store, state, lo, total)
        seglen = seglen or total
        while lo < total:
            hi = min(lo + seglen, total)
            state = stage.run_segment(ctx, store, state, lo, hi)
            if self.checkpoint is not None and hi < total:
                with telemetry.span("checkpoint"):
                    self._save_partial(i, stage, store, state, hi, total)
            lo = hi
        return stage.finalize(ctx, store, state)

    def run(self, x, *, resume: bool = False) -> ArtifactStore:
        """Execute the pipeline on input points x (n, D).

        Returns the :class:`~repro.core.artifacts.ArtifactStore` holding
        the exported artifacts (a Mapping - ``art["embedding"]`` etc.).
        The host spans ``repro:fit``, ``repro:stage:<name>`` and
        ``repro:checkpoint`` cover the dispatch of the work: nothing here
        waits for the device (see :mod:`repro.core.telemetry`).
        """
        with telemetry.span("fit"):
            return self._run(x, resume=resume)

    def _run(self, x, *, resume: bool) -> ArtifactStore:
        backend = self.ctx.backend
        store = ArtifactStore()
        store.exports = self.exports
        store.put(
            "x", x, producer="input", placement=backend.placement_of(x)
        )
        start, seg_state, seg_lo = 0, None, 0
        if resume and self.checkpoint is not None:
            point = self._find_resume_point()
            start = point.start
            if point.artifacts is not None:
                x_saved = point.artifacts.get("x")
                if x_saved is not None and (
                    x_saved.shape != x.shape
                    or not _same_input(x_saved, x)
                ):
                    raise ValueError(
                        f"resume: checkpointed input (shape "
                        f"{x_saved.shape}) does not match the points "
                        f"run() was given (shape {x.shape}); "
                        "pass the original points, a fresh checkpoint "
                        "directory, or resume=False"
                    )
                for k, v in point.artifacts.items():
                    if k == "x":
                        continue  # keep the caller's (already placed) x
                    placement = point.placements.get(k)
                    store.put(
                        k, backend.place(v, placement),
                        producer=point.producers.get(k, "checkpoint"),
                        placement=placement,
                    )
                if point.seg_state is not None:
                    prefix = SEGMENT_STATE_KEY + "/"
                    seg_state = {
                        k: backend.place(
                            v, point.placements.get(prefix + k)
                        )
                        for k, v in point.seg_state.items()
                    }
                    seg_lo = point.seg_lo
        for i in range(start, len(self.stages)):
            stage = self.stages[i]
            with telemetry.span("stage:" + stage.name):
                if _is_resumable(stage):
                    out = self._run_resumable(
                        i, stage, store,
                        seg_state if i == start else None,
                        seg_lo if i == start else 0,
                    )
                else:
                    out = stage.run(self.ctx, store)
                for k, v in out.items():
                    store.put(
                        k, v, producer=stage.name,
                        placement=backend.placement_of(v),
                    )
                store.prune(self._live_after(i))
            if self.checkpoint is not None:
                with telemetry.span("checkpoint"):
                    self._save_boundary(i, stage, store)
        if self.checkpoint is not None:
            with telemetry.span("checkpoint"):
                self.checkpoint.wait()
        return store
