"""Updatable manifolds: fold accepted stream arrivals back into the base
geodesics.

The paper notes its exact pipeline and streaming Isomap are "orthogonal
... and in fact both methods could be combined when the initial batch is
large".  :class:`~repro.core.streaming.StreamingMapper` is the read side
of that combination; this module is the write side: an update engine
that batches *accepted* arrivals (gated by the Schoeneman-style
streaming error metric, :func:`repro.core.metrics.stream_mapping_error`)
and expands the fitted geodesic system from (n, n) to (n+m, n+m) without
refitting - megaman's updatable-data-structure lesson applied to the
geodesic matrix itself.

Border expansion
----------------
The m arrivals bring kNN edges E (m, n) against the base set and F
(m, m) among themselves (:func:`border_edges`, same construction and
symmetrization as the pipeline's ``graph`` stage).  Because the base
system A is already min-plus *closed*, the grown closure never needs a
full Floyd-Warshall - five fused steps suffice
(:func:`expand_geodesics`):

  1. ``B = min(E, E (x) A)``      border rows relaxed through the base
                                  (fused ``minplus_border`` kernel)
  2. ``S = min(F, B (x) E^T)``    new-block paths through the base
  3. ``D = FW(min(S, S^T))``      close the (m, m) new block
  4. ``B' = min(B, D (x) B)``     fold multi-arrival hops into the border
  5. ``A' = min(A, B'^T (x) B')`` one seeded rank-m sweep over the
                                  interior (fused ``minplus_update``)

Every step seeds its accumulator from the destination, so no min-plus
product intermediate is materialized - in particular no (n, n) one
(asserted by jaxpr inspection in the tests and the serving smoke bench,
the same discipline as ``benchmarks/run.py --only apsp_phase2``).  On a
:class:`~repro.core.pipeline.MeshBackend` the same five steps run as a
``shard_map`` against the tile-sharded base matrix (partial min-plus
products reduced with ``pmin``), and the grown matrix is resharded
across the mesh.

Contract: the grown matrix is *exactly* the APSP closure of the
augmented graph (base graph + arrival edges) - bit-identical to a
from-scratch blocked Floyd-Warshall when path sums are exactly
representable, within float tolerance otherwise (path sums associate
differently).  Rewiring the *base* points' neighbourhoods is explicitly
out of scope: that is the "initial batch is large" assumption the paper
makes for the streaming combination, and the acceptance gate exists to
reject arrivals for which it fails.

Durability
----------
:class:`GeodesicUpdater` appends every accepted batch to an update log
persisted through a :class:`~repro.checkpoint.CheckpointManager` (under
``<checkpoint_dir>/updates``): append-only entries of (batch, D) points
plus the flush sizes they triggered - O(batch) per absorb, never the
cumulative history, never the grown O(n^2) state.  Entries chain into
*generations* (a fresh server starts a new one, shadowing any stale log
in a reused directory).  A restored server replays the newest generation
with the original flush grouping (:meth:`GeodesicUpdater.replay`),
reproducing the absorbed state deterministically instead of losing it;
the log's identity params (k, fit-time base size) are validated first,
the same fingerprint discipline as pipeline resume.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import metrics
from repro.kernels import ops

#: manifest marker distinguishing update-log checkpoints from pipeline
#: stage checkpoints
UPDATE_LOG_KEY = "update_log"

#: subdirectory of a pipeline checkpoint directory holding the update log
UPDATE_LOG_DIR = "updates"


class TornUpdateLogWarning(UserWarning):
    """A torn/truncated update-log entry was detected and skipped.

    Checkpoint saves are atomic (tmp dir + ``os.replace``), so a torn
    entry means the filesystem itself lost the write (power cut,
    truncated copy, bad disk).  Replaying bytes like that as a flush
    group would silently corrupt the manifold, so the log readers stop
    at the first torn entry instead: replay covers the longest complete
    prefix of the generation, bit-identical to the writer's state at
    that log position, and this warning names the torn step."""


@dataclasses.dataclass(frozen=True)
class LogEntry:
    """One complete, decoded update-log entry (replication unit)."""

    step: int              # monotonic log step
    gen: int               # generation id (first step of the chain)
    x: np.ndarray          # (count, D) points accepted by the absorb call
    flushes: list          # flush-group sizes the call triggered
    manifest: dict         # full manifest (identity params etc.)


def read_log_entries(
    log_dir: str, *, after_step: int = 0, warn: bool = True
):
    """Decode every complete update-log entry in ``log_dir`` (the
    ``<checkpoint_dir>/updates`` directory itself) with step >
    `after_step`, in step order: the incremental read the replication
    tailer polls (and :meth:`GeodesicUpdater.find_log`'s backbone).

    Returns ``(entries, torn_step)``: ``entries`` is a list of
    :class:`LogEntry`; ``torn_step`` is the step number of the first
    torn/unreadable entry (manifest unparseable, arrays truncated or
    missing), or None.  Reading STOPS at a torn entry - later entries'
    flush groups consume the accepted stream cumulatively, so replaying
    past a hole would apply the wrong points - and a
    :class:`TornUpdateLogWarning` is emitted when `warn`.  Entries that
    are not update-log entries at all (foreign checkpoints in a shared
    directory) are skipped without stopping the scan.
    """
    import warnings

    from repro.checkpoint import CheckpointManager

    if not os.path.isdir(log_dir):
        return [], None
    mgr = CheckpointManager(log_dir)
    entries: list[LogEntry] = []
    torn_step = None
    for step in mgr.all_steps():
        if step <= after_step:
            continue
        try:
            manifest = mgr.read_manifest(step)
        except (OSError, ValueError):
            torn_step = step          # unreadable manifest: torn entry
            break
        if not manifest.get(UPDATE_LOG_KEY):
            continue                  # foreign checkpoint, not a log hole
        try:
            data = mgr.restore_flat(step)
            x = np.asarray(data["x"], dtype=np.float32)
        except Exception:             # truncated npz, missing arrays, ...
            torn_step = step
            break
        entries.append(LogEntry(
            step=step,
            gen=int(manifest.get("gen", step)),
            x=x,
            flushes=[int(s) for s in manifest.get("flushes", [])],
            manifest=manifest,
        ))
    if torn_step is not None and warn:
        warnings.warn(
            f"update log under {log_dir!r}: entry step {torn_step} is "
            "torn/unreadable (partial write?); replaying the complete "
            f"prefix only ({len(entries)} newer entr"
            f"{'y' if len(entries) == 1 else 'ies'} read, entries past "
            "the torn step are dropped - they would consume the wrong "
            "points)",
            TornUpdateLogWarning,
            stacklevel=2,
        )
    return entries, torn_step


# ------------------------------------------------------------ edge build ----


@functools.partial(jax.jit, static_argnames=("k",))
def border_edges(x_new: jax.Array, x_base: jax.Array, *, k: int):
    """kNN edges of an arrival batch against base ∪ batch.

    Returns (e, f): e (m, n) edge weights arrival->base, f (m, m)
    symmetrized edge weights among the arrivals (0 diagonal), inf where
    no edge - Euclidean lengths, the same semantics as
    :func:`repro.core.graph.knn_to_graph` restricted to the border.
    """
    m, n = x_new.shape[0], x_base.shape[0]
    k = min(k, n + m - 1)
    d2b = ops.pairwise_sq_dists(x_new, x_base)           # (m, n)
    d2n = ops.pairwise_sq_dists(x_new, x_new)            # (m, m)
    d2n = jnp.where(jnp.eye(m, dtype=bool), jnp.inf, d2n)
    cand = jnp.concatenate([d2b, d2n], axis=1)           # (m, n+m)
    neg, idx = jax.lax.top_k(-cand, k)
    vals = jnp.sqrt(jnp.maximum(-neg, 0.0)).reshape(-1)
    rows = jnp.repeat(jnp.arange(m), k)
    full = jnp.full((m, n + m), jnp.inf, dtype=jnp.float32)
    full = full.at[rows, idx.reshape(-1)].min(vals)
    e = full[:, :n]
    f = jnp.minimum(full[:, n:], full[:, n:].T)          # symmetric graph
    f = jnp.where(jnp.eye(m, dtype=bool), 0.0, f)
    return e, f


# -------------------------------------------------------- local expansion ----


@functools.partial(jax.jit, static_argnames=("mode",))
def expand_geodesics(
    a: jax.Array,    # (n, n) closed base system
    e: jax.Array,    # (m, n) border edges arrival->base
    f: jax.Array,    # (m, m) edges among the arrivals
    *,
    mode: str = "auto",
) -> jax.Array:
    """Expand the closed (n, n) system to the closed (n+m, n+m) system.

    Exact APSP closure of the augmented graph (see module docstring); no
    min-plus product intermediate is materialized at any step.
    """
    b = ops.minplus_border(e, a, mode=mode)              # (m, n)
    s = ops.minplus_update(f, b, e.T, mode=mode)         # (m, m)
    s = jnp.minimum(s, s.T)      # exact-arithmetic symmetry, enforced in fp
    d = ops.floyd_warshall(s, mode=mode)                 # close the new block
    b = ops.minplus_panel_row(d, b, mode=mode)           # B' = min(B, D(x)B)
    a = ops.minplus_update(a, b.T, b, mode=mode)         # rank-m interior
    top = jnp.concatenate([a, b.T], axis=1)
    bot = jnp.concatenate([b, d], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def expand_geodesics_materializing(
    a: jax.Array, e: jax.Array, f: jax.Array, *, mode: str = "auto"
) -> jax.Array:
    """The unfused oracle composition of :func:`expand_geodesics`: every
    min-plus product materialized, then min'd with its seed.

    Bit-identical to the fused form (min is exact, each contraction term
    is one rounded addition) while carrying strictly more product-shaped
    jaxpr intermediates - the baseline the fusion-discipline assertions
    (tier-1, ``--only apsp_phase2``, the absorb smoke) compare against.
    Shared here so the check exists in exactly one place.
    """
    b = jnp.minimum(e, ops.minplus(e, a, mode=mode))
    s = jnp.minimum(f, ops.minplus(b, e.T, mode=mode))
    s = jnp.minimum(s, s.T)
    d = ops.floyd_warshall(s, mode=mode)
    b = jnp.minimum(b, ops.minplus(d, b, mode=mode))
    a = jnp.minimum(a, ops.minplus(b.T, b, mode=mode))
    top = jnp.concatenate([a, b.T], axis=1)
    bot = jnp.concatenate([b, d], axis=1)
    return jnp.concatenate([top, bot], axis=0)


@functools.partial(jax.jit, static_argnames=("mode",))
def expand_panel(
    panel: jax.Array,  # (m, n) landmark geodesics of the base
    e: jax.Array,      # (g, n) border edges arrival->base
    f: jax.Array,      # (g, g) edges among the arrivals
    *,
    mode: str = "auto",
) -> jax.Array:
    """Expand the (m, n) landmark panel to (m, n+g) — the sparse regime's
    absorb, never materializing anything O(n^2).

    Landmark-mediated closure: paths between arrivals may route through
    the base only via a landmark (the same approximation the sparse
    regime's triangulation already makes), so the fold is

      1. ``P_new = E (x) panel^T``          arrival->landmark through the
                                            base (g, m)
      2. ``S = min(F, P_new (x) P_new^T)``  arrival block, landmark-mediated
      3. ``D = FW(min(S, S^T))``            close the (g, g) block
      4. ``P_new' = min(P_new, D (x) P_new)``  multi-arrival hops
      5. ``panel' = min(panel, P_new'^T (x) E)``  shorter landmark->base
                                            routes through the arrivals
      6. concat ``panel'`` with ``P_new'^T``  -> (m, n+g)

    Steps 2/4/5 use the seeded fused kernels, so no min-plus product
    intermediate is materialized (same discipline as
    :func:`expand_geodesics`); every array is (g, n), (g, m), (g, g) or
    (m, n).  Exact on the landmark-mediated metric; agrees with a
    sparse-regime refit over base + arrivals to triangulation tolerance.
    """
    p_new = ops.minplus(e, panel.T, mode=mode)            # (g, m)
    s = ops.minplus_update(f, p_new, p_new.T, mode=mode)  # (g, g)
    s = jnp.minimum(s, s.T)
    d = ops.floyd_warshall(s, mode=mode)                  # close arrivals
    p_new = ops.minplus_panel_row(d, p_new, mode=mode)    # (g, m)
    panel = ops.minplus_update(panel, p_new.T, e, mode=mode)   # (m, n)
    return jnp.concatenate([panel, p_new.T], axis=1)      # (m, n+g)


def augmented_graph(x_base, x_new, *, k: int, base_graph=None):
    """The (n+m, n+m) augmented adjacency the absorb path closes: the
    base kNN graph block plus the arrivals' :func:`border_edges`,
    symmetrized.  The refit oracles (tier-1 + the absorb smoke bench)
    run a from-scratch APSP over this graph to check an absorb."""
    from repro.core import graph as graph_mod, knn as knn_mod

    x_base = jnp.asarray(x_base)
    x_new = np.atleast_2d(np.asarray(x_new, dtype=np.float32))
    n, m = x_base.shape[0], x_new.shape[0]
    if base_graph is None:
        d, i = knn_mod.knn_blocked(x_base, k=k, block=min(128, n))
        base_graph = graph_mod.knn_to_graph(d, i, n=n)
    e, f = border_edges(jnp.asarray(x_new), x_base, k=k)
    g = np.full((n + m, n + m), np.inf, np.float32)
    g[:n, :n] = np.asarray(base_graph)
    g[n:, :n] = np.asarray(e)
    g[:n, n:] = np.asarray(e).T
    g[n:, n:] = np.asarray(f)
    return np.minimum(g, g.T)


# ------------------------------------------------------ sharded expansion ----


@functools.lru_cache(maxsize=None)
def make_expand_sharded(
    mesh, n: int, m: int,
    data_axis: str = "data",
    model_axis: str = "model",
    mode: str = "auto",
    fused: bool = True,
):
    """Build the jit'd shard_map body of the mesh border expansion.

    The base matrix stays tile-sharded P(data, model); e/f are
    replicated (m is a small arrival batch).  Contractions against the
    sharded dimensions compute local partial min-plus products reduced
    with ``pmin``; the closed border is all-gathered (O(m n) bytes)
    before the fully local rank-m interior sweep.  Returns
    ``fn(a, e, f) -> (a_interior, border, new_block)`` with the interior
    still tile-sharded and the borders replicated - the backend
    assembles and reshards the grown matrix.

    fused=False swaps the seeded kernels for materializing
    ``min(seed, minplus(...))`` compositions - bit-identical values,
    strictly more tile-shaped intermediates; the baseline the mesh
    absorb smoke's fusion-discipline assertion compares against.
    """
    from repro.sharding.logical import folded_axis_index, mesh_axis_size

    pd = mesh_axis_size(mesh, data_axis)
    pm = mesh_axis_size(mesh, model_axis)
    if n % pd or n % pm:
        raise ValueError(
            f"base-set size {n} must divide the mesh axes ({pd}, {pm})"
        )
    nr, nc = n // pd, n // pm

    def panel_row(d, r):
        if fused:
            return ops.minplus_panel_row(d, r, mode=mode)
        return jnp.minimum(r, ops.minplus(d, r, mode=mode))

    def update(g, c, r):
        if fused:
            return ops.minplus_update(g, c, r, mode=mode)
        return jnp.minimum(g, ops.minplus(c, r, mode=mode))

    def shard_fn(a_loc, e, f):
        di = folded_axis_index(data_axis)
        mi = folded_axis_index(model_axis)
        # 1. border rows through the base: contract over this shard's
        #    rows of A, pmin across the data axis completes the min
        e_rows = jax.lax.dynamic_slice_in_dim(e, di * nr, nr, 1)  # (m, nr)
        part = ops.minplus(e_rows, a_loc, mode=mode)              # (m, nc)
        b_loc = jax.lax.pmin(part, data_axis)
        e_cols = jax.lax.dynamic_slice_in_dim(e, mi * nc, nc, 1)  # (m, nc)
        b_loc = jnp.minimum(e_cols, b_loc)                        # seed E
        # 2.-3. new-block paths through the base, closed with FW
        s_part = ops.minplus(b_loc, e_cols.T, mode=mode)          # (m, m)
        s = jnp.minimum(f, jax.lax.pmin(s_part, model_axis))
        s = jnp.minimum(s, s.T)
        d = ops.floyd_warshall(s, mode=mode)
        # 4. fold multi-arrival hops into the border (column chunk local)
        b_loc = panel_row(d, b_loc)                               # (m, nc)
        # 5. rank-m interior sweep: fully local once the closed border
        #    is gathered (O(m n) bytes - the only bulk communication)
        b_full = jax.lax.all_gather(b_loc, model_axis, axis=1, tiled=True)
        b_rows = jax.lax.dynamic_slice_in_dim(b_full, di * nr, nr, 1)
        a_loc = update(a_loc, b_rows.T, b_loc)
        return a_loc, b_full, d

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(), P()),
        out_specs=(P(data_axis, model_axis), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


# ------------------------------------------------------------ the engine ----


@dataclasses.dataclass
class UpdateConfig:
    """Knobs of the absorb path.

    threshold: acceptance bound on the Schoeneman-style streaming error
    (dimensionless; arrivals scoring above it are served but not
    absorbed).
    multiple: flush-group granularity; None uses the backend's
    ``absorb_multiple`` (1 locally, lcm of the mesh axes on a mesh so
    the grown matrix keeps dividing the tile grid).
    log_dir: persist the update log here (a CheckpointManager directory;
    :meth:`StreamingMapper.from_checkpoint` replays it on restore).
    max_iter/tol: power-iteration knobs of the re-embedding, matching
    the pipeline defaults so an absorb matches a refit.
    """

    threshold: float = 0.15
    multiple: int | None = None
    log_dir: str | None = None
    max_iter: int = 100
    tol: float = 1e-9


@dataclasses.dataclass
class AbsorbReport:
    """What one :meth:`StreamingMapper.absorb` call did."""

    submitted: int          # points in the batch
    accepted: int           # passed the acceptance gate
    rejected: int           # served-only (off-manifold / unreliable)
    absorbed: int           # folded into the published system this call
    buffered: int           # accepted but awaiting a full flush group
    version: int            # serving version after this call
    errors: np.ndarray      # per-point gate scores, aligned with the batch


class GeodesicUpdater:
    """Batches accepted arrivals and folds them into the geodesic system.

    Owned by a :class:`~repro.core.streaming.StreamingMapper`; all entry
    points run under the mapper's absorb lock (single writer - readers
    are lock-free via the versioned snapshot).
    """

    def __init__(self, mapper, cfg: UpdateConfig):
        self.mapper = mapper
        self.cfg = cfg
        self.multiple = cfg.multiple or getattr(
            mapper.backend, "absorb_multiple", 1
        )
        if self.multiple < 1:
            raise ValueError(f"flush multiple must be >= 1: {self.multiple}")
        self._pending: list[np.ndarray] = []   # accepted, awaiting flush
        self._pending_count = 0
        self._flushes: list[int] = []          # flush-group sizes, in order
        self._n_base0 = int(mapper.n_base)     # fit-time base size
        self._gen: int | None = None           # update-log generation id
        self._log = None
        self._next_step = 1
        if cfg.log_dir:
            from repro.checkpoint import CheckpointManager

            # append-only log: every entry of the current generation is
            # needed for replay, so retention must never GC the chain
            # (entries are tiny (batch, D) payloads)
            self._log = CheckpointManager(cfg.log_dir, keep=1_000_000_000)
            # single writer under the mapper's absorb lock: scan the
            # directory once, then number steps from memory (a per-absorb
            # listdir would grow linearly with the log)
            self._next_step = (self._log.latest_step() or 0) + 1

    # ------------------------------------------------------------ gating --

    def gate(self, x_new) -> np.ndarray:
        """Schoeneman-style streaming errors of an arrival batch against
        the *current* serving version (m,)."""
        snap = self.mapper.snapshot()
        x_new = jnp.asarray(x_new)
        # anchor search on the gathered base: kNN selection must be
        # backend-independent (a sharded distance computation can flip
        # near-tie neighbours), so gate decisions replay identically
        xb = jnp.asarray(np.asarray(snap["x"]))
        yb = jnp.asarray(np.asarray(snap["embedding"]))
        k = self.mapper.k
        d2 = ops.pairwise_sq_dists(x_new, xb)            # (m, n)
        neg, idx = jax.lax.top_k(-d2, k)
        anchor_d = jnp.sqrt(jnp.maximum(-neg, 0.0))      # (m, k)
        y_new = self.mapper._map_batch(x_new, snap)      # (m, d)
        scale = jnp.sqrt(jnp.mean(snap["mean_sq"]))      # RMS geodesic scale
        err = metrics.stream_mapping_error(
            anchor_d, y_new, yb[idx], scale
        )
        return np.asarray(err)

    # ------------------------------------------------------------ absorb --

    def absorb(self, x_new) -> AbsorbReport:
        """Gate, buffer, and (when a full flush group is ready) fold an
        arrival batch into the geodesic system, publishing the grown
        artifacts as a new serving version."""
        x_new = np.atleast_2d(np.asarray(x_new, dtype=np.float32))
        m = x_new.shape[0]
        if m == 0:
            errors = np.zeros((0,), np.float32)
            accepted = x_new
        else:
            errors = self.gate(x_new)
            accepted = x_new[errors <= self.cfg.threshold]
        n_acc = accepted.shape[0]
        if n_acc:
            self._pending.append(accepted)
            self._pending_count += n_acc
        absorbed = self._flush_ready()
        # log on any accepted points AND on any flush: a flush can fire
        # from previously-buffered points on a call that accepted none
        # (e.g. replay re-buffered a tail under a smaller multiple) - an
        # unlogged flush would make the next replay diverge from the
        # state this server published
        if (n_acc or absorbed) and self._log is not None:
            self._save_log(accepted, [absorbed] if absorbed else [])
        return AbsorbReport(
            submitted=m,
            accepted=n_acc,
            rejected=m - n_acc,
            absorbed=absorbed,
            buffered=self._pending_count,
            version=self.mapper.version,
            errors=errors,
        )

    def _flush_ready(self) -> int:
        """Fold every complete flush group out of the buffer; returns the
        number of points folded in."""
        group_sz = (self._pending_count // self.multiple) * self.multiple
        if group_sz == 0:
            return 0
        buf = np.concatenate(self._pending, axis=0)
        group, tail = buf[:group_sz], buf[group_sz:]
        self._pending = [tail] if tail.shape[0] else []
        self._pending_count = tail.shape[0]
        self._expand(group)
        self._flushes.append(group_sz)
        return group_sz

    def _expand(self, group: np.ndarray):
        """One flush: grow the geodesic system by `group`, re-embed it
        under the mapper's objective, and republish atomically."""
        from repro.core.pipeline import PipelineConfig

        mapper = self.mapper
        backend = mapper.backend
        snap = mapper.snapshot()
        a = snap["geodesics"]
        # edge construction on the gathered base: the kNN selection must
        # be identical on every backend (a sharded distance computation
        # can flip near-tie neighbours, which is a *structural* graph
        # change) - local and mesh absorbs agree, and a replay on a
        # different backend reproduces the same augmented graph
        xb = np.asarray(snap["x"])
        e, f = border_edges(
            jnp.asarray(group), jnp.asarray(xb), k=mapper.k
        )
        grown = backend.expand_geodesics(a, e, f)
        x_grown = backend.place_rows(
            jnp.asarray(np.concatenate([xb, group], axis=0))
        )
        cfg = PipelineConfig(
            k=mapper.k, d=snap["embedding"].shape[1],
            max_iter=self.cfg.max_iter, tol=self.cfg.tol,
            objective=mapper.objective.name,
        )
        out = mapper.objective.reembed_dense(backend, cfg, grown)
        mapper._publish(
            x=x_grown,
            geodesics=grown,
            mean_sq=backend.row_mean_sq(grown),
            **out,
        )

    # ---------------------------------------------------------- durability --

    @property
    def last_log_step(self) -> int:
        """Step number of the newest entry this writer has durably
        logged (0 before the first append) - the position a replica must
        reach for :meth:`ReplicatedMapperFleet.sync` to consider it
        caught up."""
        return self._next_step - 1

    def _save_log(self, new_points: np.ndarray, flush_delta: list[int]):
        """Append one update-log entry: the points accepted by THIS call
        plus the flush sizes it triggered.

        The log is append-only (O(batch) write per absorb, never the
        cumulative history, never the grown O(n^2) state): replay
        reconstructs the accepted stream by concatenating the entries of
        one *generation* in step order.  A generation is identified by
        the step number of its first entry; a fresh (non-restored)
        updater starts a new generation, so a stale log left in a reused
        checkpoint directory is shadowed, never concatenated with.
        """
        # monotonic step numbering: always strictly newer than anything
        # already in the log directory (scanned once at construction)
        step = self._next_step
        self._next_step += 1
        if self._gen is None:
            self._gen = step
        # blocking: the log is the durability story for absorbed traffic
        # and the entry is tiny - an absorb only reports success once its
        # log entry is on disk
        self._log.save(
            step,
            {"x": np.asarray(new_points, dtype=np.float32)},
            blocking=True,
            manifest_extra={
                UPDATE_LOG_KEY: True,
                "gen": self._gen,
                "flushes": [int(s) for s in flush_delta],
                "count": int(new_points.shape[0]),
                "k": self.mapper.k,
                "n_base0": self._n_base0,
                "threshold": self.cfg.threshold,
                "multiple": self.multiple,
                "objective": self.mapper.objective.name,
            },
        )

    def replay(self, x_all: np.ndarray, flushes: list[int],
               gen: int | None = None):
        """Re-apply a restored update log: the original flush groups are
        expanded in order, exactly as recorded (gating skipped - they
        were already accepted; the recorded grouping is used verbatim,
        not re-derived from this backend's flush multiple), then the
        unflushed tail is re-buffered - the restored server reaches the
        same version chain deterministically.  ``gen`` adopts the
        restored generation so later absorbs append to the same chain.

        Incremental: points already buffered (by an earlier replay
        call's unflushed tail) are consumed FIRST - flush groups eat the
        cumulative accepted stream from the front, so a log-tailing
        replica can feed entries one at a time and reach bit-identically
        the same state as one whole-log replay (whole-log restore is the
        empty-buffer special case).
        """
        self._gen = gen if gen is not None else self._gen
        x_all = np.asarray(x_all, dtype=np.float32)
        if self._pending:
            x_all = np.concatenate([*self._pending, x_all], axis=0)
            self._pending = []
            self._pending_count = 0
        off = 0
        for sz in flushes:
            group = x_all[off:off + sz]
            try:
                self._expand(group)
            except ValueError as e:
                raise ValueError(
                    f"update-log replay: recorded flush group of {sz} "
                    f"points cannot be expanded on this backend ({e}); "
                    "restore onto a backend whose mesh divides the "
                    "logged group sizes, or discard the update log"
                ) from e
            self._flushes.append(sz)
            off += sz
        tail = x_all[off:]
        if tail.shape[0]:
            self._pending.append(tail)
            self._pending_count += tail.shape[0]

    @staticmethod
    def find_log(base_dir: str):
        """Reassemble the newest update-log generation under a pipeline
        checkpoint directory; returns (x_all, flushes, manifest) or
        None - x_all/flushes are the concatenated entries of the
        generation in step order, manifest is the newest entry's (its
        identity params apply to the whole generation).  Foreign steps
        (pipeline checkpoints sharing the directory) are skipped; a
        torn/truncated entry stops the scan (with a
        :class:`TornUpdateLogWarning`), so replay covers the longest
        complete prefix instead of consuming the wrong points."""
        entries, _ = read_log_entries(os.path.join(base_dir, UPDATE_LOG_DIR))
        if not entries:
            return None
        newest = entries[-1]
        chain = [e for e in entries if e.gen == newest.gen]
        x_all = np.concatenate([e.x for e in chain], axis=0)
        flushes = [s for e in chain for s in e.flushes]
        return x_all, flushes, newest.manifest


class LandmarkGeodesicUpdater(GeodesicUpdater):
    """Absorb engine of the sparse regime: folds accepted arrivals into
    the (m, n) landmark panel instead of the (n, n) base matrix.

    Owned by a :class:`~repro.core.streaming.LandmarkStreamingMapper`;
    gating, buffering, flush grouping, and the durable update log are all
    inherited — only the expansion differs (:func:`expand_panel` plus a
    landmark-MDS re-embed, everything O(m * (n+g))).  The landmark set is
    fixed at fit time: arrivals densify the panel's columns, they never
    become landmarks (the "initial batch is large" assumption again — the
    fitted landmarks already cover the manifold the arrivals land on).
    """

    def _expand(self, group: np.ndarray):
        from repro.core.pipeline import PipelineConfig
        from repro.core.sparse import panel_row_mean_sq

        mapper = self.mapper
        backend = mapper.backend
        snap = mapper.snapshot()
        # edge construction on the gathered base (same backend-independence
        # rationale as the dense absorb: kNN ties must not flip per shard)
        xb = np.asarray(snap["x"])
        e, f = border_edges(
            jnp.asarray(group), jnp.asarray(xb), k=mapper.k
        )
        grown = expand_panel(jnp.asarray(np.asarray(snap["panel"])), e, f)
        cfg = PipelineConfig(
            k=mapper.k, d=snap["embedding"].shape[1],
            max_iter=self.cfg.max_iter, tol=self.cfg.tol,
            objective=mapper.objective.name,
        )
        out = mapper.objective.reembed_panel(
            backend, cfg, grown, jnp.asarray(np.asarray(snap["lm_idx"]))
        )
        place = getattr(backend, "place_replicated", jnp.asarray)
        mapper._publish(
            x=place(jnp.asarray(np.concatenate([xb, group], axis=0))),
            panel=place(grown),
            mean_sq=place(panel_row_mean_sq(grown)),
            **{key: place(v) for key, v in out.items()},
        )
