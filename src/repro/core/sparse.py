"""Sparse scale regime: landmark geodesics without the (n, n) base.

The dense pipeline materializes three O(n^2) arrays (graph, evolving APSP
state, Gram).  This module is the regime that never does: geodesics are
computed *only from m hierarchically-selected landmarks* (m << n) by a
bucketed delta-stepping solver over the padded-CSR kNN graph
(:func:`repro.core.graph.knn_to_padded_csr`), producing an (m, n) panel
that every downstream consumer — embedding, serving, absorb — reads
instead of the base matrix.  Peak residency is O(n * k + m * n).

Exactness.  The solver is pull-based Jacobi Bellman-Ford with a
delta-stepping threshold mask: each sweep relaxes every node against its
neighbours, but only tentative distances below the current bucket bound
``hi = delta * (t + 1)`` may propagate
(:func:`repro.kernels.ops.frontier_relax`).  Termination is what makes it
exact: a batch is *settled* iff the last masked sweep changed nothing AND
every finite tentative distance is below ``hi`` — at that point no finite
value is masked, so the masked sweep coincides with the unmasked one, and
an unchanged unmasked sweep is precisely the Bellman-Ford fixed point,
i.e. the exact SSSP.  ``hi`` rises unboundedly with the round counter, so
the loop always reaches that state (disconnected targets stay +inf and
are excluded from the bound check).  On exact-weight graphs (integer
edge lengths) every path sum is exactly representable, so the panel rows
are bit-identical to the dense APSP oracle restricted to landmark rows;
on real data they agree to accumulated-rounding tolerance.

The knobs (``bs`` sources per launch, ``bn`` node tile, ``bucket``
sweeps per convergence check) come from the frontier autotuner
(:func:`repro.kernels.autotune.frontier_config`); ``delta`` is derived
from the mean finite edge weight so a round of ``bucket`` sweeps and the
threshold bound advance at the same rate.  A batch's tentative distances
are nodes-major, (n, bs) with the sources on the lanes, for its whole
solve; only its settled rows are transposed into the (m, n) panel.  The
settled panel is the minimum over paths of left-to-right float32 path
sums, so it does not depend on ``bs``, ``bucket`` or ``delta``.

Mesh execution is embarrassingly parallel: landmark rows are sharded over
the *folded* (data, model) axis (every device, not every data row, owns
m/p sources), the padded-CSR graph is replicated, and each device runs
the identical solver on its rows with **zero collectives** in the hot
loop.  The embed stage replicates the (m, n) panel once at the end —
within the O(m * n) budget — and runs the general landmark MDS.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import spectral
from repro.core.postprocess import clamp_disconnected, embedding_from_eig
from repro.kernels import autotune, ops

# ------------------------------------------------------ dense-budget gate --

ENV_DENSE_BYTES = "REPRO_DENSE_BYTES"
#: default single-fit budget for the dense regime (bytes); ~16 GiB covers
#: one accelerator's HBM with headroom for XLA temporaries
DEFAULT_DENSE_BYTES = 16 * 2**30


class DenseBudgetError(ValueError):
    """The dense (n, n) regime was asked to fit a problem it cannot hold."""


def dense_fit_bytes(n: int, *, itemsize: int = 4) -> int:
    """Peak dense-fit residency: graph + evolving APSP state + Gram, each
    (n, n) — the three simultaneously-live O(n^2) arrays of the exact
    path."""
    return 3 * n * n * itemsize


def dense_budget_ok(n: int, *, itemsize: int = 4) -> bool:
    budget = int(os.environ.get(ENV_DENSE_BYTES, DEFAULT_DENSE_BYTES))
    return dense_fit_bytes(n, itemsize=itemsize) <= budget


def check_dense_budget(n: int, *, itemsize: int = 4) -> int:
    """Refuse the dense regime beyond the byte budget (``REPRO_DENSE_BYTES``
    overrides; default :data:`DEFAULT_DENSE_BYTES`).  Called by the dense
    GraphStage so an over-budget dense fit fails *before* allocating
    anything O(n^2), with a message pointing at the sparse regime."""
    budget = int(os.environ.get(ENV_DENSE_BYTES, DEFAULT_DENSE_BYTES))
    need = dense_fit_bytes(n, itemsize=itemsize)
    if need > budget:
        raise DenseBudgetError(
            f"dense regime needs ~{need / 2**30:.1f} GiB for n={n} "
            f"(budget {budget / 2**30:.1f} GiB, {ENV_DENSE_BYTES} to "
            "override); use the sparse regime (PipelineConfig("
            "regime='sparse') / --regime sparse)"
        )
    return need


def default_landmarks(n: int) -> int:
    """Default landmark budget: ~4 sqrt(n), floored at 16, capped at n —
    m * n panel memory grows as n^{3/2} while covering the manifold at a
    density that keeps triangulation error flat in the benchmarks."""
    return max(16, min(n, 4 * int(round(np.sqrt(n)))))


# --------------------------------------------------------------- solver ----


def frontier_delta(w: jax.Array, bucket: int) -> jax.Array:
    """Bucket width: a round of ``bucket`` sweeps extends paths by up to
    ``bucket`` hops, i.e. ~``bucket *`` (mean finite edge weight) of
    distance — growing ``hi`` at the same rate keeps the threshold just
    ahead of the frontier.  Floored so an edgeless graph still
    terminates (hi must grow)."""
    fin = jnp.isfinite(w)
    mean_w = jnp.sum(jnp.where(fin, w, 0.0)) / jnp.maximum(
        jnp.sum(fin), 1
    )
    return jnp.maximum(mean_w * bucket, 1e-6).astype(jnp.float32)


def _solve_batch(
    src, nbr, w, delta, *, bucket: int, bn: int, mode: str, max_rounds: int
):
    """Exact SSSP for one fixed-shape source batch.

    src (bs,) int32 node indices -> (n, bs) geodesic distances (+inf where
    unreachable), nodes-major: the sources ride the lanes for the whole
    loop, and the caller transposes once per batch.  See the module
    docstring for the settled-iff-exact argument; ``max_rounds`` is a
    runaway backstop only (the bound check fails before it in any
    terminating run)."""
    bs = src.shape[0]
    n = nbr.shape[0]
    dist = jnp.full((n, bs), jnp.inf, dtype=jnp.float32)
    dist = dist.at[src, jnp.arange(bs)].set(0.0)

    def cond(carry):
        _, t, done = carry
        return (~done) & (t < max_rounds)

    def body(carry):
        d, t, _ = carry
        hi = delta * (t + 1).astype(jnp.float32)

        def sweep(_, dd):
            return ops.frontier_relax(dd, nbr, w, hi, mode=mode, bn=bn)

        new = jax.lax.fori_loop(0, bucket, sweep, d)
        finite_max = jnp.max(jnp.where(jnp.isfinite(new), new, -jnp.inf))
        settled = jnp.all(new == d) & (finite_max < hi)
        return new, t + 1, settled

    dist, _, _ = jax.lax.while_loop(
        cond, body, (dist, jnp.int32(0), jnp.bool_(False))
    )
    return dist


@jax.named_scope("sparse_geodesics")
def _segment_rows(
    nbr, w, lm_idx, panel, lo, hi, delta, *,
    row0, ml: int, bs: int, bucket: int, bn: int, mode: str,
    max_rounds: int,
):
    """Solve landmark batches [lo, hi) of one device's ``ml``-row panel
    slice starting at global row ``row0`` (0 and m locally).  The last
    batch is shifted back to stay fixed-shape (``start = min(b*bs,
    ml-bs)``): overlapped rows are recomputed to the same deterministic
    values, so shapes never vary and the kernel jits once."""

    def one_batch(b, panel):
        start = jnp.minimum(b * bs, ml - bs)
        src = jax.lax.dynamic_slice(lm_idx, (row0 + start,), (bs,))
        d = _solve_batch(
            src, nbr, w, delta,
            bucket=bucket, bn=bn, mode=mode, max_rounds=max_rounds,
        )
        return jax.lax.dynamic_update_slice(panel, d.T, (start, 0))

    return jax.lax.fori_loop(lo, hi, one_batch, panel)


def sparse_units(m: int, bs: int) -> int:
    """Landmark batches needed to cover m rows at batch size bs."""
    return max(1, -(-m // bs))


@functools.partial(
    jax.jit,
    static_argnames=("bs", "bucket", "bn", "mode", "max_rounds"),
)
def sparse_panel_segment(
    nbr, w, lm_idx, panel, lo, hi, delta, *,
    bs: int, bucket: int, bn: int, mode: str, max_rounds: int = 100_000,
):
    """Local-backend segment: solve landmark batches [lo, hi) into the
    (m, n) panel.  lo/hi/delta are traced, so one executable serves every
    segment length (the engine's checkpoint_secs calibration relies on
    this)."""
    m = lm_idx.shape[0]
    return _segment_rows(
        nbr, w, lm_idx, panel, lo, hi, delta,
        row0=jnp.int32(0), ml=m, bs=min(bs, m), bucket=bucket, bn=bn,
        mode=mode, max_rounds=max_rounds,
    )


def sssp_panel(
    nbr, w, lm_idx, *, mode: str = "auto",
    cfg: autotune.FrontierConfig | None = None,
):
    """One-shot exact landmark panel (unsegmented; tests and small fits).

    nbr/w (n, deg) padded CSR, lm_idx (m,) -> (m, n) geodesics, +inf
    where unreachable."""
    n, deg = nbr.shape
    m = lm_idx.shape[0]
    if cfg is None:
        cfg = autotune.frontier_config(n, deg, m)
    bs = min(cfg.bs, m)
    panel = jnp.full((m, n), jnp.inf, dtype=jnp.float32)
    delta = frontier_delta(w, cfg.bucket)
    units = sparse_units(m, bs)
    return sparse_panel_segment(
        nbr, w, jnp.asarray(lm_idx, jnp.int32), panel,
        jnp.int32(0), jnp.int32(units), delta,
        bs=bs, bucket=cfg.bucket, bn=cfg.bn, mode=mode,
    )


# ------------------------------------------------------- mesh (shard_map) --


@functools.lru_cache(maxsize=None)
def make_sparse_segment_sharded(
    mesh, m: int, n: int, deg: int, mode: str, *,
    bs: int, bucket: int, bn: int, max_rounds: int = 100_000,
    data_axis: str = "data", model_axis: str = "model",
):
    """Build the jit'd shard_map solving landmark batches on a mesh.

    Landmark rows are sharded over the folded (data, model) axis — the
    solver is embarrassingly parallel over sources, so folding both axes
    uses every device with zero collectives in the loop; the padded-CSR
    graph and lm_idx ride replicated.  Each device runs batches [lo, hi)
    of its OWN m/p-row slice, so a global segment advances p batches at
    once and checkpoints carry the P((data, model), None) panel placement
    (round-tripped by the artifact store's tuple-axis specs)."""
    from repro.sharding.logical import folded_axis_index, mesh_axis_size

    folded = (data_axis, model_axis)
    p = mesh_axis_size(mesh, folded)
    if m % p:
        raise ValueError(
            f"landmark count {m} must divide the folded mesh ({p} devices)"
        )
    ml = m // p

    def shard_fn(nbr, w, lm_idx, panel_loc, lo, hi, delta):
        di = folded_axis_index(folded)
        return _segment_rows(
            nbr, w, lm_idx, panel_loc, lo, hi, delta,
            row0=di * ml, ml=ml, bs=min(bs, ml), bucket=bucket, bn=bn,
            mode=mode, max_rounds=max_rounds,
        )

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(folded, None), P(), P(), P()),
        out_specs=P(folded, None),
        check_vma=False,
    )
    return jax.jit(fn)


# ------------------------------------------------------------- embedding ---


class PanelEmbedding(NamedTuple):
    embedding: jax.Array           # (n, d) triangulated points
    landmark_embedding: jax.Array  # (m, d)
    pinv: jax.Array                # (m, d) triangulation operator
    mean2: jax.Array               # (m,) row means of the landmark block
    eigenvalues: jax.Array         # (d,)
    iterations: jax.Array


@functools.partial(jax.jit, static_argnames=("d", "max_iter"))
@jax.named_scope("sparse_embed")
def landmark_mds_general(
    dl: jax.Array, lm_idx: jax.Array, *, d: int,
    max_iter: int = 100, tol: float = 1e-9,
) -> PanelEmbedding:
    """Landmark MDS + triangulation for landmarks at arbitrary indices.

    Unlike :func:`repro.core.isomap._landmark_mds` (which assumes the
    landmark columns are ``dl[:, :m]``), the sparse panel's landmarks are
    hierarchical-FPS picks scattered through the base — the (m, m)
    landmark block is gathered by ``lm_idx``.  Same de Silva & Tenenbaum
    math otherwise: double-center the landmark block, top-d
    power-iteration eigenbasis, distance-based triangulation of all n
    points from their landmark-geodesic columns."""
    dl2 = jnp.square(dl)
    sub = dl2[:, lm_idx]                                # (m, m)
    mu_row = jnp.mean(sub, axis=1, keepdims=True)
    mu_col = jnp.mean(sub, axis=0, keepdims=True)
    mu = jnp.mean(sub)
    bmat = -0.5 * (sub - mu_row - mu_col + mu)
    eig = spectral.power_iteration(bmat, d=d, max_iter=max_iter, tol=tol)
    lam = jnp.maximum(eig.eigenvalues, 1e-12)
    l_emb = embedding_from_eig(eig.eigenvectors, lam)   # (m, d)
    pinv = eig.eigenvectors / jnp.sqrt(lam)[None, :]    # (m, d)
    mean2 = jnp.mean(sub, axis=1)                       # (m,)
    y = -0.5 * (dl2 - mean2[:, None]).T @ pinv          # (n, d)
    return PanelEmbedding(
        embedding=y, landmark_embedding=l_emb, pinv=pinv, mean2=mean2,
        eigenvalues=eig.eigenvalues, iterations=eig.iterations,
    )


def panel_row_mean_sq(panel: jax.Array) -> jax.Array:
    """Per-base-point mean squared landmark geodesic (n,) — the sparse
    analogue of :func:`repro.core.streaming.geodesic_row_mean_sq`, used
    by the serving gate's scale estimate."""
    return jax.jit(lambda p: jnp.mean(jnp.square(p), axis=0))(panel)


@functools.partial(jax.jit, static_argnames=("k",))
@jax.named_scope("map")
def map_new_points_panel(
    x_new, x_base, panel, pinv, mean2, *, k: int
):
    """Triangulate new points through the landmark panel.

    Anchors each new point on its k nearest base points, extends the
    landmark geodesics by one Euclidean hop (exactly like the dense
    mapper's min-over-anchors), then applies the fitted triangulation
    operator.  Returns (y (b, d), geo_lm (b, m)) — the landmark columns
    are reused by the absorb path as the new points' panel columns."""
    d2 = ops.pairwise_sq_dists(x_new, x_base)
    nd, idx = jax.lax.top_k(-d2, k)
    anchor_d = jnp.sqrt(jnp.maximum(-nd, 0.0))          # (b, k)
    cols = jnp.transpose(panel[:, idx], (1, 2, 0))      # (b, k, m)
    geo_lm = jnp.min(anchor_d[:, :, None] + cols, axis=1)   # (b, m)
    y = -0.5 * (jnp.square(geo_lm) - mean2[None, :]) @ pinv
    return y, geo_lm


# --------------------------------------------------------------- stages ----


class CSRGraphStage:
    """kNN lists -> padded-CSR adjacency, never the dense scatter."""

    name = "csr_graph"
    requires = ("x", "knn_dists", "knn_idx")
    provides = ("csr_nbr", "csr_w")

    def run(self, ctx, art):
        nbr, w = ctx.backend.csr_graph(
            ctx.cfg, art["knn_dists"], art["knn_idx"], n=art["x"].shape[0]
        )
        return {"csr_nbr": nbr, "csr_w": w}


class LandmarkSelectStage:
    """Hierarchical FPS landmark selection (host-side, deterministic).

    ``m`` is identity (``params``): a checkpointed panel answers exactly
    one landmark set.  On a mesh the effective count is rounded down to
    a multiple of the folded device count so the panel rows shard; the
    exported ``lm_idx`` is the ground truth for the realized m."""

    name = "landmarks"
    requires = ("x", "knn_dists")
    provides = ("lm_idx",)
    exports = ("lm_idx",)
    params = ("m",)

    def __init__(self, m: int | None = None):
        self.m = m

    def _effective_m(self, ctx, n: int) -> int:
        m = self.m or getattr(ctx.cfg, "landmarks", 0) or default_landmarks(n)
        m = min(m, n)
        mult = getattr(ctx.backend, "landmark_multiple", 1)
        if m % mult:
            m = max(mult, (m // mult) * mult)
        return m

    def run(self, ctx, art):
        from repro.core.landmarks import hierarchical_landmarks

        n = art["x"].shape[0]
        m = self._effective_m(ctx, n)
        # host-gathered inputs: selection must be bit-deterministic and
        # backend-independent (same rationale as the updater's gate)
        lm = hierarchical_landmarks(
            np.asarray(art["x"]), np.asarray(art["knn_dists"]), m=m
        )
        if lm.shape[0] < m:
            # duplicate points collapsed some picks: top up from the
            # smallest unused indices to keep m (and mesh divisibility)
            unused = np.setdiff1d(np.arange(n), lm)
            lm = np.sort(np.concatenate([lm, unused[: m - lm.shape[0]]]))
        return {
            "lm_idx": ctx.backend.place_replicated(
                jnp.asarray(lm, dtype=jnp.int32)
            )
        }


class SparseGeodesicStage:
    """Exact landmark geodesics over the CSR graph, as a ResumableStage.

    Units are landmark batches (the frontier autotuner's lane-width
    batch), state is the growing (m, n) panel — so
    checkpoint/resume and ``--checkpoint-secs`` calibration work through
    the engine unchanged, and a kill mid-panel re-enters at the recorded
    batch.  ``segment_requires`` keeps the CSR graph + landmark set in
    mid-stage checkpoints: unlike APSP, the panel state does not subsume
    the graph (every batch relaxes against it)."""

    name = "sparse_geodesics"
    requires = ("csr_nbr", "csr_w", "lm_idx")
    provides = ("panel",)
    exports = ("panel",)
    segment_requires = ("csr_nbr", "csr_w", "lm_idx")

    def num_units(self, ctx, art):
        return ctx.backend.sparse_num_units(
            ctx.cfg, art["lm_idx"].shape[0], art["csr_nbr"].shape
        )

    def init_state(self, ctx, art):
        return {
            "panel": ctx.backend.sparse_init(
                ctx.cfg, art["lm_idx"].shape[0], art["csr_nbr"].shape[0]
            )
        }

    def run_segment(self, ctx, art, state, lo, hi):
        panel = ctx.backend.sparse_segment(
            ctx.cfg, art["csr_nbr"], art["csr_w"], art["lm_idx"],
            state["panel"], lo, hi,
        )
        return {"panel": panel}

    def finalize(self, ctx, art, state):
        return {"panel": ctx.backend.clamp(ctx.cfg, state["panel"])}

    def run(self, ctx, art):
        """Unsegmented fallback (direct use outside the engine)."""
        state = self.init_state(ctx, art)
        state = self.run_segment(ctx, art, state, 0, self.num_units(ctx, art))
        return self.finalize(ctx, art, state)


class SparseEmbedStage:
    """Embed the landmark panel through the configured objective.

    The spectral artifact set (lm_pinv/lm_mean2 and friends) is always
    produced - it is the serving contract of
    :class:`~repro.core.streaming.LandmarkStreamingMapper` - and
    non-spectral objectives append their extras (stress values, path
    landmark sets) on top, declared via ``panel_extras`` so liveness
    pruning and checkpoints see them.
    """

    name = "sparse_embed"
    params = ("objective_id",)

    _BASE_PROVIDES = (
        "embedding", "landmark_embedding", "lm_pinv", "lm_mean2",
        "eigenvalues", "iterations",
    )
    _BASE_EXPORTS = (
        "embedding", "lm_pinv", "lm_mean2", "eigenvalues", "iterations",
    )

    def __init__(self, objective=None):
        from repro.core.embedding import get_objective

        self.objective = get_objective(objective)
        extras = tuple(self.objective.panel_extras)
        self.provides = self._BASE_PROVIDES + extras
        self.exports = self._BASE_EXPORTS + extras
        self.objective_id = self.objective.identity()

    requires = ("panel", "lm_idx")

    def run(self, ctx, art):
        return self.objective.embed_panel(
            ctx.backend, ctx.cfg, art["panel"], art["lm_idx"]
        )


def sparse_isomap_stages(m: int | None = None, objective=None):
    """The sparse-regime chain: shared kNN front, CSR assembly, landmark
    selection, segmented frontier geodesics, panel embedding."""
    from repro.core.pipeline import KNNStage

    return [
        KNNStage(), CSRGraphStage(), LandmarkSelectStage(m),
        SparseGeodesicStage(), SparseEmbedStage(objective),
    ]
