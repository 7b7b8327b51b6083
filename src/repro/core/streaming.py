"""Streaming-Isomap hook (paper SV: the authors' streaming method is
"orthogonal to the one we present here, and in fact both methods could be
combined in case when the initial batch is large").

This module is that combination point: an exact Isomap pipeline run over
the large initial batch produces the ``x`` / ``geodesics`` / ``embedding``
artifacts; :func:`map_new_points` places stream arrivals on the learned
manifold in O(k n) per point - kNN against the base set, one min-plus
relaxation through the base geodesics, and the L-Isomap triangulation
against the embedding's eigenbasis.  :class:`StreamingMapper` packages
that as a serving object constructed straight from pipeline artifacts
(in-memory or restored from a stage-boundary checkpoint) and maps arrival
batches with bounded peak memory.

Like every pipeline stage, the mapper dispatches through the backend
protocol: on a :class:`~repro.core.pipeline.LocalBackend` the relaxation is
the single-device :func:`map_new_points`; on a
:class:`~repro.core.pipeline.MeshBackend` it runs as a ``shard_map`` over
the data axis against the row-sharded geodesics
(:func:`map_new_points_sharded`) - the anchor rows are completed with a
masked psum and the ``min(anchor_d + A[idx])`` relaxation is computed on
each device's column chunk, so per-query work and memory scale 1/p with the
mesh.

The mapper is no longer read-only: :meth:`StreamingMapper.absorb` folds
accepted arrivals back into the base geodesics (the updatable-manifold
engine, :mod:`repro.core.update`), republishing
``x``/``geodesics``/``embedding`` as an atomic new version
(:class:`~repro.core.artifacts.VersionedArtifacts`) - readers are
lock-free and keep serving the version they captured, so queries never
block on an absorb.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import telemetry
from repro.core.artifacts import VersionedArtifacts
from repro.kernels import ops

# Floor for the per-column eigenvalue estimate in the triangulation
# pseudo-inverse.  ``embedding_from_eig`` clamps negative eigenvalues to
# exactly 0, so a degenerate column in the base embedding would otherwise
# divide by zero and emit NaN coordinates for every streamed point.
# Matches the landmark tail's floor in ``core/isomap.py``.
_EIG_FLOOR = 1e-12


def _eigenbasis_pinv(y_base):
    """Pseudo-inverse of the base embedding's eigenbasis for the L-Isomap
    triangulation; shared by the local and sharded paths."""
    n = y_base.shape[0]
    lam = jnp.sum(y_base * y_base, axis=0) / n           # eigvals / n
    lam = jnp.maximum(lam, _EIG_FLOOR)
    return y_base / (lam[None, :] * n)                   # (n, d) pseudo-inv


@jax.jit
def geodesic_row_mean_sq(a_base: jax.Array) -> jax.Array:
    """Row means of the squared base geodesics - the O(n^2) constant of the
    triangulation.  Serving objects compute it once per fit, not per batch."""
    return jnp.mean(jnp.square(a_base), axis=1)


@functools.partial(jax.jit, static_argnames=("k",))
@jax.named_scope("map")
def map_new_points(
    x_new: jax.Array,      # (m, D) stream arrivals
    x_base: jax.Array,     # (n, D) initial batch
    a_base: jax.Array,     # (n, n) exact geodesics of the initial batch
    y_base: jax.Array,     # (n, d) embedding of the initial batch
    *,
    k: int = 10,
    mean_sq: jax.Array | None = None,   # (n,) precomputed row means of a^2
):
    """Returns (m, d) coordinates for the new points."""
    k = min(k, x_base.shape[0])
    # geodesic estimate: through the k nearest base anchors
    d2 = ops.pairwise_sq_dists(x_new, x_base)            # (m, n)
    neg, idx = jax.lax.top_k(-d2, k)                     # k anchors each
    anchor_d = jnp.sqrt(jnp.maximum(-neg, 0.0))          # (m, k)
    # d_geo(new, j) = min_a anchor_d[, a] + A[idx[, a], j]
    geo = jnp.min(
        anchor_d[:, :, None] + a_base[idx], axis=1
    )                                                     # (m, n)

    # L-Isomap triangulation against the base embedding's eigenbasis
    pinv = _eigenbasis_pinv(y_base)
    if mean_sq is None:
        mean_sq = jnp.mean(jnp.square(a_base), axis=1)   # (n,)
    y_new = -0.5 * (jnp.square(geo) - mean_sq[None, :]) @ pinv
    return y_new


@functools.partial(jax.jit, static_argnames=("k",))
def new_point_geodesics(
    x_new: jax.Array, x_base: jax.Array, a_base: jax.Array, *, k: int = 10
):
    """The geodesic-estimate front half of :func:`map_new_points` on its
    own: (m, n) estimated geodesics from each arrival to every base point
    via the k-anchor min-plus relaxation.  Non-spectral embedding
    objectives consume these directly (stress placement fits coordinates
    to them instead of triangulating through the eigenbasis)."""
    k = min(k, x_base.shape[0])
    d2 = ops.pairwise_sq_dists(x_new, x_base)            # (m, n)
    neg, idx = jax.lax.top_k(-d2, k)
    anchor_d = jnp.sqrt(jnp.maximum(-neg, 0.0))          # (m, k)
    return jnp.min(anchor_d[:, :, None] + a_base[idx], axis=1)


# ------------------------------------------------------------- sharded ----


@functools.lru_cache(maxsize=None)
def _make_row_mean_sq_sharded(mesh, n, data_axis, model_axis):
    """Sharded :func:`geodesic_row_mean_sq`: row means of the squared
    tile-sharded geodesics via the shared sharded-matvec (A^{o2} @ 1/n)."""
    from repro.core import spectral
    from repro.sharding.logical import mesh_axis_size

    nc = n // mesh_axis_size(mesh, model_axis)

    def shard_fn(a_loc):
        return spectral.matvec_sharded(
            jnp.square(a_loc), jnp.full((n, 1), 1.0 / n, a_loc.dtype),
            data_axis=data_axis, model_axis=model_axis, nc=nc,
        )[:, 0]                                          # (n,) replicated

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=P(data_axis, model_axis), out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def _geo_shard_body(x_new, xb_loc, a_loc, k, nr, data_axis, model_axis, mode):
    """Per-device body of the sharded geodesic estimate, shared by the
    triangulating mapper and the raw :func:`new_point_geodesics` hook."""
    from repro.sharding.logical import folded_axis_index

    di = folded_axis_index(data_axis)
    # kNN anchors against the row-sharded base set: per-shard distance
    # chunks, gathered so every device ranks the same full row
    d2_loc = ops.pairwise_sq_dists(x_new, xb_loc, mode=mode)  # (m, nr)
    d2 = jax.lax.all_gather(d2_loc, data_axis, axis=1, tiled=True)
    neg, idx = jax.lax.top_k(-d2, k)                 # (m, k) global ids
    anchor_d = jnp.sqrt(jnp.maximum(-neg, 0.0))      # (m, k)
    # complete the k anchor rows of the tile-sharded geodesics: each
    # device contributes the rows it owns, a masked psum fills the rest
    owner = idx // nr                                # (m, k)
    local = jnp.clip(idx - di * nr, 0, nr - 1)
    rows = jnp.where(
        (owner == di)[:, :, None], a_loc[local], 0.0
    )                                                # (m, k, nc)
    rows = jax.lax.psum(rows, data_axis)
    # anchor relaxation on this device's column chunk of the geodesics
    geo_loc = jnp.min(anchor_d[:, :, None] + rows, axis=1)   # (m, nc)
    return jax.lax.all_gather(geo_loc, model_axis, axis=1, tiled=True)


@functools.lru_cache(maxsize=None)
def _make_new_point_geo_sharded(mesh, n, k, data_axis, model_axis, mode):
    """Sharded :func:`new_point_geodesics`: same per-device relaxation as
    the mapper, without the triangulation tail (replicated (m, n) out)."""
    from repro.sharding.logical import mesh_axis_size

    pd = mesh_axis_size(mesh, data_axis)
    pm = mesh_axis_size(mesh, model_axis)
    if n % pd or n % pm:
        raise ValueError(
            f"base-set size {n} must divide the mesh axes ({pd}, {pm})"
        )
    nr = n // pd

    def shard_fn(x_new, xb_loc, a_loc):
        return _geo_shard_body(
            x_new, xb_loc, a_loc, k, nr, data_axis, model_axis, mode
        )

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis, model_axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def new_point_geodesics_sharded(
    x_new: jax.Array,
    x_base: jax.Array,
    a_base: jax.Array,
    mesh,
    *,
    k: int = 10,
    data_axis: str = "data",
    model_axis: str = "model",
    mode: str = "auto",
):
    """Mesh-sharded :func:`new_point_geodesics` (same sharding contract
    as :func:`map_new_points_sharded`)."""
    n = x_base.shape[0]
    fn = _make_new_point_geo_sharded(
        mesh, n, min(k, n), data_axis, model_axis, mode
    )
    return fn(x_new, x_base, a_base)


@functools.lru_cache(maxsize=None)
def _make_map_new_points_sharded(
    mesh, n, k, data_axis, model_axis, mode
):
    """Build the jit'd shard_map body for :func:`map_new_points_sharded`.

    Cached per (mesh, n, k) so repeated serving calls reuse one compiled
    executable per arrival-batch shape."""
    from repro.sharding.logical import mesh_axis_size

    pd = mesh_axis_size(mesh, data_axis)
    pm = mesh_axis_size(mesh, model_axis)
    if n % pd or n % pm:
        raise ValueError(
            f"base-set size {n} must divide the mesh axes ({pd}, {pm})"
        )
    nr = n // pd

    @jax.named_scope("map")
    def shard_fn(x_new, xb_loc, a_loc, y_base, mean_sq):
        geo = _geo_shard_body(
            x_new, xb_loc, a_loc, k, nr, data_axis, model_axis, mode
        )
        # replicated triangulation against the precomputed row statistics
        pinv = _eigenbasis_pinv(y_base)
        return -0.5 * (jnp.square(geo) - mean_sq[None, :]) @ pinv

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(), P(data_axis), P(data_axis, model_axis), P(), P(),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def map_new_points_sharded(
    x_new: jax.Array,
    x_base: jax.Array,
    a_base: jax.Array,
    y_base: jax.Array,
    mesh,
    *,
    k: int = 10,
    data_axis: str = "data",
    model_axis: str = "model",
    mode: str = "auto",
    mean_sq: jax.Array | None = None,
):
    """Mesh-sharded :func:`map_new_points`: x_base row-sharded over
    `data_axis`, a_base tile-sharded, x_new/y_base replicated.  Matches the
    local path within float tolerance (the relaxation itself is exact; only
    the row-mean reduction order differs).  Pass a precomputed ``mean_sq``
    (see :class:`StreamingMapper`) to skip the per-call O(n^2/p) row
    reduction."""
    n = x_base.shape[0]
    if mean_sq is None:
        mean_sq = _make_row_mean_sq_sharded(
            mesh, n, data_axis, model_axis
        )(a_base)
    fn = _make_map_new_points_sharded(
        mesh, n, min(k, n), data_axis, model_axis, mode
    )
    return fn(x_new, x_base, a_base, y_base, mean_sq)


class StreamingMapper:
    """Serves new-point queries from a fitted pipeline's artifacts.

    The pipeline's ``x`` (base points), ``geodesics`` and ``embedding``
    artifacts are exactly the state this mapper needs - they are reusable
    across restarts via the pipeline's stage-boundary checkpoints:

        pipe = ManifoldPipeline(checkpoint=mgr)
        art  = pipe.run(x_base)
        mapper = StreamingMapper.from_artifacts(art, k=10)
        ...crash...
        mapper = StreamingMapper.from_checkpoint(mgr, k=10)  # no refit

    Queries are mapped in `batch` chunks so peak memory stays at
    O(batch * n) regardless of arrival-burst size.

    backend: a pipeline backend (LocalBackend default).  Passing the
    pipeline's MeshBackend serves queries with the geodesics row-sharded
    over the mesh (state is ``device_put`` onto the mesh once, at
    construction).

    The serving state lives in a
    :class:`~repro.core.artifacts.VersionedArtifacts` publication point:
    :meth:`absorb` folds accepted arrivals into the geodesic system and
    swaps the serving version atomically (one reference assignment;
    queries read one snapshot for their whole batch and never take a
    lock).  ``update`` configures the absorb path
    (:class:`repro.core.update.UpdateConfig`); the default config is
    created lazily on first absorb.
    """

    def __init__(
        self,
        x_base: jax.Array,
        geodesics: jax.Array,
        embedding: jax.Array,
        *,
        k: int = 10,
        batch: int = 256,
        backend=None,
        update=None,
        objective=None,
    ):
        from repro.core.embedding import get_objective

        n = x_base.shape[0]
        assert geodesics.shape == (n, n), (geodesics.shape, n)
        assert embedding.shape[0] == n, (embedding.shape, n)
        if backend is None:
            from repro.core.pipeline import LocalBackend

            backend = LocalBackend()
        self.backend = backend
        self.k = min(k, n)
        self.batch = batch
        self.objective = get_objective(objective)
        if getattr(backend, "kind", "local") == "sharded":
            from jax.sharding import NamedSharding

            repl = NamedSharding(backend.mesh, P())
            x_base = backend.place_rows(jnp.asarray(x_base))
            geodesics = jax.device_put(
                jnp.asarray(geodesics), backend.tile_spec
            )
            embedding = jax.device_put(jnp.asarray(embedding), repl)
        else:
            x_base = jnp.asarray(x_base)
            geodesics = jnp.asarray(geodesics)
            embedding = jnp.asarray(embedding)
        self._versions = VersionedArtifacts({
            "x": x_base,
            "geodesics": geodesics,
            "embedding": embedding,
            # the O(n^2) triangulation constant: once per fit, not per batch
            "mean_sq": self.backend.row_mean_sq(geodesics),
        })
        self._update_cfg = update
        self._updater = None
        self._absorb_lock = threading.Lock()

    #: the updater class :meth:`absorb` instantiates on first use; None
    #: means the default dense-regime :class:`repro.core.update.
    #: GeodesicUpdater` (resolved lazily to keep the import one-way)
    UPDATER_CLS = None

    def _updater_cls(self):
        from repro.core.update import GeodesicUpdater

        return self.UPDATER_CLS or GeodesicUpdater

    # ------------------------------------------------- versioned state ----

    def snapshot(self):
        """One immutable serving generation (lock-free read); use the
        same snapshot for every array a single request touches."""
        return self._versions.current

    def _publish(self, **artifacts):
        """Swap in a new serving generation (called by the updater under
        the absorb lock)."""
        return self._versions.publish(artifacts)

    @property
    def version(self) -> int:
        """Serving version: 0 at fit, +1 per absorbed flush group."""
        return self._versions.version

    def await_version(self, version: int, timeout: float | None = None
                      ) -> bool:
        """Block until a serving generation >= `version` is published
        (True) or `timeout` passes (False) - replication tests use it to
        wait for a replica's cutover without polling."""
        return self._versions.await_version(version, timeout)

    @property
    def x_base(self):
        return self._versions.current["x"]

    @property
    def geodesics(self):
        return self._versions.current["geodesics"]

    @property
    def embedding(self):
        return self._versions.current["embedding"]

    @property
    def mean_sq(self):
        return self._versions.current["mean_sq"]

    @property
    def n_base(self) -> int:
        """Size of the (possibly grown) base set being served."""
        return self._versions.current["x"].shape[0]

    #: the artifacts this mapper serves from - must be *exported* by the
    #: fitted pipeline (liveness pruning drops everything else)
    SERVING_ARTIFACTS = ("x", "geodesics", "embedding")

    @classmethod
    def from_artifacts(
        cls, artifacts, *, k: int = 10, batch: int = 256, backend=None,
        update=None, objective=None,
    ):
        """Build from a ManifoldPipeline.run() result (an ArtifactStore
        Mapping, or any plain dict with the same keys).

        The store only retains *exported* artifacts - the engine drops
        consumed intermediates as their last consumer runs - so serving
        state is exactly the export set this mapper names in
        ``SERVING_ARTIFACTS``.  A pipeline configured with exports that
        drop any of them fails here with a clear message instead of a
        KeyError deep in the constructor.
        """
        missing = [a for a in cls.SERVING_ARTIFACTS if a not in artifacts]
        if missing:
            exports = getattr(artifacts, "exports", ())
            raise KeyError(
                f"artifacts {missing} absent from the fitted pipeline "
                f"result (available: {sorted(artifacts)}"
                + (f", exports: {sorted(exports)}" if exports else "")
                + f"); the pipeline must export "
                f"{'/'.join(cls.SERVING_ARTIFACTS)} for streaming serving"
            )
        return cls(
            *(artifacts[a] for a in cls.SERVING_ARTIFACTS),
            k=k, batch=batch, backend=backend, update=update,
            objective=objective,
        )

    @classmethod
    def from_checkpoint(
        cls, manager, *, k: int = 10, batch: int = 256, backend=None,
        update=None, replay_updates: bool = True, objective=None,
    ):
        """Restore the newest pipeline checkpoint holding the needed
        artifacts (i.e. any stage boundary at or after ``eigen``), then
        replay the persisted update log (if any) so absorbed stream
        arrivals survive the restart instead of being lost.

        Tolerant scan (same contract as the pipeline's resume scan): a
        concurrently GC'd or partially written step - manifest unreadable,
        or missing the ``keys`` field - is skipped, falling back to the
        next-older boundary instead of crashing the serving process.

        Objective identity (same discipline as the pipeline's resume
        fingerprints): a checkpoint fitted under one embedding objective
        must not be served as another - the spectral eigenbasis is not a
        stress answer - so a recorded ``config.objective`` that differs
        from the requested one raises instead of silently serving."""
        from repro.core.embedding import get_objective

        obj = get_objective(objective)
        for step in reversed(manager.all_steps()):
            try:
                manifest = manager.read_manifest(step)
            except OSError:
                continue
            if set(cls.SERVING_ARTIFACTS) <= set(manifest.get("keys", [])):
                saved_obj = (manifest.get("config") or {}).get(
                    "objective", "spectral"
                )
                if saved_obj != obj.name:
                    raise ValueError(
                        f"checkpoint step {step} in {manager.directory} "
                        f"was fitted under objective {saved_obj!r}; "
                        f"serving it as {obj.name!r} would answer from "
                        "the wrong embedding.  Restore with "
                        f"objective={saved_obj!r} or refit"
                    )
                try:
                    art = manager.restore_flat(step)
                except (OSError, KeyError):
                    # step GC'd between the manifest read and the array
                    # load, or arrays missing: fall back to an older one
                    continue
                mapper = cls.from_artifacts(
                    art, k=k, batch=batch, backend=backend, update=update,
                    objective=obj,
                )
                if replay_updates:
                    mapper.replay_update_log(manager.directory)
                return mapper
        raise FileNotFoundError(
            f"no checkpoint in {manager.directory} holds the "
            f"{'/'.join(cls.SERVING_ARTIFACTS)} artifacts (pipeline not "
            "run through its serving stages?)"
        )

    def _map_batch(self, x_new: jax.Array, snap=None) -> jax.Array:
        snap = snap if snap is not None else self._versions.current
        return self.objective.map_new_points(
            self.backend, x_new, snap, k=self.k
        )

    def __call__(self, x_new: jax.Array) -> jax.Array:
        """Map (m, D) arrivals -> (m, d) manifold coordinates, batched.

        The whole call serves from one captured version: an absorb
        landing mid-call cannot mix generations across chunks."""
        snap = self._versions.current
        with telemetry.span("map:put"):
            x_new = jnp.asarray(x_new)
        m = x_new.shape[0]
        d = snap["embedding"].shape[1]
        if m == 0:
            return jnp.zeros((0, d), snap["embedding"].dtype)
        if m <= self.batch:
            return self._map_batch(x_new, snap)
        outs = []
        for lo in range(0, m, self.batch):
            outs.append(self._map_batch(x_new[lo : lo + self.batch], snap))
        return jnp.concatenate(outs, axis=0)

    def map_stream(self, batches) -> np.ndarray:
        """Consume an iterable of arrival batches; returns stacked coords."""
        outs = [np.asarray(self(b)) for b in batches]
        if not outs:
            return np.zeros((0, self.embedding.shape[1]))
        return np.concatenate(outs, axis=0)

    # ------------------------------------------------------------ absorb --

    def absorb(self, x_new):
        """Fold an arrival batch into the base geodesics.

        Arrivals are gated by the Schoeneman-style streaming error
        metric (accepted: mapped near-isometrically, safe to densify the
        manifold with; rejected: served but not absorbed), buffered, and
        - whenever a full flush group is ready - expanded into the
        geodesic system and republished as a new serving version.
        Returns an :class:`repro.core.update.AbsorbReport`.

        Single writer: concurrent absorbs serialize on a lock; readers
        never take it (update-log replay bypasses this entirely via
        :meth:`replay_update_log`).
        """
        from repro.core.update import UpdateConfig

        with self._absorb_lock:
            if self._updater is None:
                self._updater = self._updater_cls()(
                    self, self._update_cfg or UpdateConfig()
                )
            return self._updater.absorb(x_new)

    def apply_log_entry(self, x, flushes, gen=None) -> None:
        """Apply one decoded update-log entry (the replication unit): the
        entry's accepted points join any previously re-buffered tail and
        its recorded flush groups are expanded verbatim.  Feeding a
        generation's entries one call at a time is bit-identical to one
        whole-log :meth:`replay_update_log` - flush groups consume the
        cumulative accepted stream front-first, and
        :meth:`~repro.core.update.GeodesicUpdater.replay` prepends the
        buffered tail.  Used by log-tailing reader replicas
        (:mod:`repro.launch.replication`); identity validation is the
        tailer's job (it sees the entry manifests)."""
        from repro.core.update import UpdateConfig

        with self._absorb_lock:
            if self._updater is None:
                self._updater = self._updater_cls()(
                    self, self._update_cfg or UpdateConfig()
                )
            self._updater.replay(x, flushes, gen=gen)

    def replay_update_log(self, checkpoint_dir: str) -> int:
        """Replay the update log persisted under `checkpoint_dir` (see
        :mod:`repro.core.update`): absorbed points are re-expanded with
        the original flush grouping.  Returns the number of replayed
        points (0 when there is no log).

        Identity check (same discipline as the pipeline's resume
        fingerprints): the log records the ``k`` and base-set size it
        was absorbed against; a mismatching log must not be silently
        replayed onto a different fit - it raises instead.
        """
        import os

        from repro.core.update import (
            UPDATE_LOG_DIR, GeodesicUpdater, UpdateConfig,
        )

        found = GeodesicUpdater.find_log(checkpoint_dir)
        if found is None:
            return 0
        x_all, flushes, manifest = found
        log_k = manifest.get("k")
        log_n0 = manifest.get("n_base0")
        if (log_k is not None and log_k != self.k) or (
            log_n0 is not None and log_n0 != self.n_base
        ):
            raise ValueError(
                f"update log under {checkpoint_dir!r} was absorbed "
                f"against k={log_k}, n_base={log_n0}; this mapper serves "
                f"k={self.k}, n_base={self.n_base} - replaying it would "
                "produce a different manifold.  Restore with matching "
                "parameters or discard the update log"
            )
        log_obj = manifest.get("objective")
        if log_obj is not None and log_obj != self.objective.name:
            raise ValueError(
                f"update log under {checkpoint_dir!r} was absorbed "
                f"under objective {log_obj!r}; this mapper serves "
                f"{self.objective.name!r} - replaying it would re-embed "
                "with a different objective than the log's published "
                "versions.  Restore with the matching objective or "
                "discard the update log"
            )
        with self._absorb_lock:
            if self._updater is None:
                import dataclasses

                cfg = self._update_cfg or UpdateConfig()
                if cfg.log_dir is None:
                    # keep appending to the same log after the restore
                    cfg = dataclasses.replace(
                        cfg,
                        log_dir=os.path.join(checkpoint_dir, UPDATE_LOG_DIR),
                    )
                self._update_cfg = cfg
                self._updater = self._updater_cls()(self, cfg)
            self._updater.replay(x_all, flushes, gen=manifest.get("gen"))
        return int(x_all.shape[0])


# --------------------------------------------------------- sparse regime ----


class LandmarkStreamingMapper(StreamingMapper):
    """Serves new-point queries from a sparse-regime fit.

    Same serving/absorb surface as :class:`StreamingMapper`, but the
    state is the sparse regime's export set — the (m, n) landmark panel
    plus the fitted triangulation operator — so nothing O(n^2) is ever
    resident.  Queries triangulate through the panel
    (:func:`repro.core.sparse.map_new_points_panel`, O(batch * k * m)
    per chunk); :meth:`absorb` folds accepted arrivals into the panel
    columns via :class:`repro.core.update.LandmarkGeodesicUpdater`.

    On a :class:`~repro.core.pipeline.MeshBackend` the serving state is
    replicated across the mesh (it is O(m * n) — the sparse budget — and
    the panel relaxation per query batch is small), which keeps the
    serve and absorb paths backend-independent bit-for-bit.
    """

    SERVING_ARTIFACTS = (
        "x", "panel", "lm_idx", "embedding", "lm_pinv", "lm_mean2",
    )

    def __init__(
        self,
        x_base: jax.Array,
        panel: jax.Array,       # (m, n) landmark geodesics
        lm_idx: jax.Array,      # (m,) landmark indices into the base
        embedding: jax.Array,   # (n, d)
        lm_pinv: jax.Array,     # (m, d) triangulation operator
        lm_mean2: jax.Array,    # (m,) landmark-block row means
        *,
        k: int = 10,
        batch: int = 256,
        backend=None,
        update=None,
        objective=None,
    ):
        from repro.core.embedding import get_objective
        from repro.core.sparse import panel_row_mean_sq

        n = x_base.shape[0]
        m = lm_idx.shape[0]
        assert panel.shape == (m, n), (panel.shape, m, n)
        assert embedding.shape[0] == n, (embedding.shape, n)
        assert lm_pinv.shape[0] == m and lm_mean2.shape == (m,), (
            lm_pinv.shape, lm_mean2.shape, m,
        )
        if backend is None:
            from repro.core.pipeline import LocalBackend

            backend = LocalBackend()
        self.backend = backend
        self.k = min(k, n)
        self.batch = batch
        self.objective = get_objective(objective)
        place = getattr(backend, "place_replicated", jnp.asarray)
        self._versions = VersionedArtifacts({
            "x": place(jnp.asarray(x_base)),
            "panel": place(jnp.asarray(panel)),
            "lm_idx": place(jnp.asarray(lm_idx)),
            "embedding": place(jnp.asarray(embedding)),
            "lm_pinv": place(jnp.asarray(lm_pinv)),
            "lm_mean2": place(jnp.asarray(lm_mean2)),
            # per-base-point mean-sq landmark geodesic: the gate's scale
            "mean_sq": place(panel_row_mean_sq(jnp.asarray(panel))),
        })
        self._update_cfg = update
        self._updater = None
        self._absorb_lock = threading.Lock()

    def _updater_cls(self):
        from repro.core.update import LandmarkGeodesicUpdater

        return self.UPDATER_CLS or LandmarkGeodesicUpdater

    @property
    def panel(self):
        return self._versions.current["panel"]

    @property
    def lm_idx(self):
        return self._versions.current["lm_idx"]

    @property
    def geodesics(self):
        raise AttributeError(
            "LandmarkStreamingMapper serves from the (m, n) landmark "
            "panel; there is no (n, n) geodesics artifact in the sparse "
            "regime (use .panel)"
        )

    def _map_batch(self, x_new: jax.Array, snap=None) -> jax.Array:
        snap = snap if snap is not None else self._versions.current
        return self.objective.map_new_points_panel(x_new, snap, k=self.k)
