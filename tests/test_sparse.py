"""Sparse scale regime tests: the frontier kernel vs its CSR oracle
(inf edges, disconnected components, padded-frontier masking), landmark
selection determinism, sparse-vs-dense geodesic agreement (bit-identical
on exact-weight graphs, 1e-5 on real data), engine-mediated resume
mid-landmark-batch, the dense-budget refusal gate, serving + absorb
through the landmark panel, and the (n, n)-free residency discipline
(asserted by jaxpr variable counting, not allocator luck)."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core import graph, sparse
from repro.core.landmarks import hierarchical_landmarks
from repro.core.pipeline import (
    LocalBackend,
    ManifoldPipeline,
    PipelineConfig,
    isomap_stages,
    stages_for,
)
from repro.core.sparse import (
    DenseBudgetError,
    LandmarkSelectStage,
    SparseGeodesicStage,
    sparse_isomap_stages,
    sssp_panel,
)
from repro.core.streaming import LandmarkStreamingMapper
from repro.data import euler_isometric_swiss_roll
from repro.kernels import autotune, ops, ref


def _random_padded_csr(rng, n, deg, *, integer=False):
    """A random padded-CSR graph + its dense (directed) adjacency twin.

    Row j lists in-neighbors: lane (j, d) is the edge nbr[j, d] -> j, the
    exact edge the pull relaxation traverses - so Floyd-Warshall on the
    twin is the fixed point of the sparse sweep, edge for edge.  Some
    lanes are padded with w = +inf, including every self-lane."""
    nbr = np.stack(
        [rng.choice(n, size=deg, replace=False) for _ in range(n)]
    ).astype(np.int32)
    if integer:
        w = rng.integers(1, 10, size=(n, deg)).astype(np.float32)
    else:
        w = rng.uniform(0.5, 10.0, size=(n, deg)).astype(np.float32)
    w[rng.uniform(size=(n, deg)) < 0.25] = np.inf  # padded lanes
    w[nbr == np.arange(n, dtype=np.int32)[:, None]] = np.inf
    g = np.full((n, n), np.inf, np.float32)
    np.fill_diagonal(g, 0.0)
    for j in range(n):
        for d in range(deg):
            if np.isfinite(w[j, d]):
                g[nbr[j, d], j] = min(g[nbr[j, d], j], w[j, d])
    return jnp.asarray(nbr), jnp.asarray(w), jnp.asarray(g)


# ------------------------------------------------- frontier kernel oracle --


@pytest.mark.parametrize("s", [4, 128])
@pytest.mark.parametrize("bn", [32, 40, 96])
def test_frontier_relax_pallas_matches_ref(rng, bn, s):
    """Pallas(interpret) vs the chunked CSR reference, nodes-major (n, s),
    bit-identical - including inf (padded) lanes, a batch under and at
    the lane width, and a bn that does not divide n (the padded-frontier
    masking path)."""
    n, deg = 96, 5
    nbr, w, _ = _random_padded_csr(np.random.default_rng(3), n, deg)
    dist = jnp.full((n, s), jnp.inf, jnp.float32)
    dist = dist.at[(jnp.arange(s) * 7) % n, jnp.arange(s)].set(0.0)
    for _ in range(2):  # a couple of sweeps so finite values spread
        dist = ops.frontier_relax(dist, nbr, w, jnp.inf, mode="ref")
    for hi in (np.inf, 4.0):
        got = np.asarray(
            ops.frontier_relax(dist, nbr, w, hi, mode="pallas", bn=bn)
        )
        want = np.asarray(ops.frontier_relax(dist, nbr, w, hi, mode="ref"))
        np.testing.assert_array_equal(got, want)
        # the ref oracle itself must be tiling-invariant
        np.testing.assert_array_equal(
            np.asarray(ref.frontier_relax_ref(dist, nbr, w, hi, chunk=7)),
            want,
        )


@pytest.mark.parametrize("s", [4, 128])
def test_frontier_threshold_masks_exactly(rng, s):
    """One masked sweep == the hand-written pull relaxation, nodes-major:
    tentative distances at or above hi must not propagate, everything
    below must - in the oracle and in the kernel on a bn that does not
    divide n."""
    n, deg = 24, 3
    nbr, w, _ = _random_padded_csr(np.random.default_rng(5), n, deg)
    dist = jnp.asarray(
        np.where(rng.uniform(size=(n, s)) < 0.5, rng.uniform(0, 8, (n, s)),
                 np.inf).astype(np.float32)
    )
    hi = 3.0
    nbr_np, w_np, d_np = (np.asarray(a) for a in (nbr, w, dist))
    g = d_np[nbr_np]                                    # (n, deg, s)
    g = np.where(g < hi, g, np.inf)
    want = np.minimum(d_np, np.min(g + w_np[:, :, None], axis=1))
    got = np.asarray(ops.frontier_relax(dist, nbr, w, hi, mode="ref"))
    np.testing.assert_array_equal(got, want)
    got = np.asarray(
        ops.frontier_relax(dist, nbr, w, hi, mode="pallas", bn=16)
    )
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------ sparse vs dense oracle ---


def test_sssp_panel_bit_identical_to_dense_fw_integer_weights():
    """On exact-weight graphs every path sum is exactly representable:
    the panel rows must be BIT-identical to dense Floyd-Warshall rows,
    including +inf for disconnected targets."""
    rng = np.random.default_rng(7)
    n, deg = 64, 6
    nbr, w, g = _random_padded_csr(rng, n, deg, integer=True)
    lm = jnp.asarray(np.sort(rng.choice(n, size=16, replace=False)),
                     jnp.int32)
    panel = np.asarray(sssp_panel(nbr, w, lm))
    dense = np.asarray(ref.floyd_warshall_ref(g))
    np.testing.assert_array_equal(panel, dense[np.asarray(lm)])


def test_sssp_panel_matches_dense_oracle_real_data():
    """Swiss-roll kNN graph: panel rows agree with the dense APSP oracle
    to accumulated-rounding tolerance."""
    from repro.core import knn

    n, k = 128, 8
    x, _ = euler_isometric_swiss_roll(n, seed=2)
    x = jnp.asarray(x)
    d, i = knn.knn_blocked(x, k=k, block=64)
    nbr, w = graph.knn_to_padded_csr(d, i, n=n)
    g = graph.knn_to_graph(d, i, n=n)
    lm = hierarchical_landmarks(np.asarray(x), np.asarray(d), m=32)
    panel = np.asarray(sssp_panel(nbr, w, jnp.asarray(lm, jnp.int32)))
    dense = np.asarray(ref.floyd_warshall_ref(g))[lm]
    np.testing.assert_allclose(panel, dense, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_sssp_panel_is_batch_invariant(mode):
    """The settled panel is the minimum over paths of left-to-right
    float32 path sums, whatever the batch: 16-wide and lane-wide (128)
    source batches - different bucket bounds, rounds and a shifted-back
    last batch - give array_equal panels on real (float) weights."""
    from repro.core import knn

    n, k, m = 256, 8, 144
    x, _ = euler_isometric_swiss_roll(n, seed=4)
    x = jnp.asarray(x)
    d, i = knn.knn_blocked(x, k=k, block=128)
    nbr, w = graph.knn_to_padded_csr(d, i, n=n)
    lm = jnp.asarray(
        hierarchical_landmarks(np.asarray(x), np.asarray(d), m=m), jnp.int32
    )
    panels = [
        np.asarray(sssp_panel(
            nbr, w, lm, mode=mode,
            cfg=autotune.FrontierConfig(bs=bs, bn=n, bucket=bucket),
        ))
        for bs, bucket in ((16, 1), (128, 2))
    ]
    assert np.isfinite(panels[0]).all()
    np.testing.assert_array_equal(panels[0], panels[1])


def test_sssp_panel_disconnected_stays_inf():
    """Two far clusters with a small k: cross-component geodesics stay
    +inf in the panel exactly where the dense oracle has them."""
    from repro.core import knn

    rng = np.random.default_rng(0)
    a = rng.normal(size=(32, 3)).astype(np.float32)
    b = rng.normal(size=(32, 3)).astype(np.float32) + 100.0
    x = jnp.asarray(np.concatenate([a, b]))
    d, i = knn.knn_blocked(x, k=4, block=32)
    nbr, w = graph.knn_to_padded_csr(d, i, n=64)
    assert int(graph.connected_components_lower_bound_csr(nbr, w)) == 2
    g = graph.knn_to_graph(d, i, n=64)
    assert int(graph.connected_components_lower_bound(g)) == 2
    lm = jnp.asarray([0, 5, 40, 60], jnp.int32)
    panel = np.asarray(sssp_panel(nbr, w, lm))
    dense = np.asarray(ref.floyd_warshall_ref(g))[np.asarray(lm)]
    np.testing.assert_array_equal(np.isinf(panel), np.isinf(dense))
    np.testing.assert_allclose(
        panel[np.isfinite(panel)], dense[np.isfinite(dense)],
        rtol=1e-5, atol=1e-5,
    )


def test_csr_graph_matches_dense_graph():
    """knn_to_padded_csr encodes exactly the knn_to_graph edge set."""
    from repro.core import knn

    n, k = 97, 5
    x, _ = euler_isometric_swiss_roll(n, seed=3)
    x = jnp.asarray(x)
    d, i = knn.knn_blocked(x, k=k, block=97)
    nbr, w = graph.knn_to_padded_csr(d, i, n=n)
    dense = np.asarray(graph.knn_to_graph(d, i, n=n))
    rebuilt = np.full((n, n), np.inf, np.float32)
    np.fill_diagonal(rebuilt, 0.0)
    nbr_np, w_np = np.asarray(nbr), np.asarray(w)
    for r in range(n):
        fin = np.isfinite(w_np[r])
        rebuilt[r, nbr_np[r, fin]] = w_np[r, fin]
    np.testing.assert_array_equal(rebuilt, dense)


def test_csr_graph_hub_overflow_doubles_width():
    """A star hub: every row lists node 0 (k=1), so the hub's
    symmetrized degree is n-1, far past the 2k starting cap — the
    device build must widen until the hub fits, losing no edge."""
    n = 40
    idx = np.zeros((n, 1), np.int32)
    idx[0, 0] = 1  # node 0's own neighbour (no self-lane in kNN lists)
    d = np.ones((n, 1), np.float32)
    nbr, w = graph.knn_to_padded_csr(jnp.asarray(d), jnp.asarray(idx), n=n)
    w_np, nbr_np = np.asarray(w), np.asarray(nbr)
    live0 = np.isfinite(w_np[0])
    assert int(live0.sum()) == n - 1  # the hub kept every spoke
    assert set(nbr_np[0, live0]) == set(range(1, n))
    # spokes still have exactly one live lane each (to the hub)
    for r in range(1, n):
        fin = np.isfinite(w_np[r])
        assert set(nbr_np[r, fin]) <= {0, 1}


def test_csr_graph_explicit_deg_pins_width():
    """An explicit deg pins the row width (no overflow retry): edges
    past the cap are dropped, padded lanes stay (self, +inf)."""
    n = 16
    idx = np.zeros((n, 1), np.int32)
    idx[0, 0] = 1
    d = np.ones((n, 1), np.float32)
    nbr, w = graph.knn_to_padded_csr(
        jnp.asarray(d), jnp.asarray(idx), n=n, deg=4
    )
    assert nbr.shape == (n, 4) and w.shape == (n, 4)
    assert int(np.isfinite(np.asarray(w)[0]).sum()) == 4  # truncated hub


def test_csr_graph_ignores_knn_pad_lanes():
    """(+inf, -1) kNN tail lanes (k > live neighbours) must not become
    edges: the build from padded lists equals the build from the same
    lists with the pad columns sliced off."""
    from repro.core import knn

    n, k = 24, 6
    x, _ = euler_isometric_swiss_roll(n, seed=5)
    x = jnp.asarray(x)
    d, i = knn.knn_blocked(x, k=k, block=n)
    pad_d = jnp.concatenate(
        [d, jnp.full((n, 2), jnp.inf, jnp.float32)], axis=1
    )
    pad_i = jnp.concatenate(
        [i, jnp.full((n, 2), -1, jnp.int32)], axis=1
    )
    nbr_a, w_a = graph.knn_to_padded_csr(d, i, n=n)
    nbr_b, w_b = graph.knn_to_padded_csr(pad_d, pad_i, n=n, deg=nbr_a.shape[1])
    np.testing.assert_array_equal(np.asarray(nbr_a), np.asarray(nbr_b))
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_b))


# ----------------------------------------------------- landmark selection --


def test_hierarchical_landmarks_deterministic():
    x, _ = euler_isometric_swiss_roll(200, seed=4)
    from repro.core import knn

    d, _ = knn.knn_blocked(jnp.asarray(x), k=8, block=100)
    a = hierarchical_landmarks(x, np.asarray(d), m=48)
    b = hierarchical_landmarks(np.asarray(x).copy(), np.asarray(d), m=48)
    np.testing.assert_array_equal(a, b)
    assert a.shape[0] == 48 and np.unique(a).shape[0] == 48
    assert a.min() >= 0 and a.max() < 200
    assert np.all(np.sort(a) == a)
    # m == n degenerates to the identity
    np.testing.assert_array_equal(
        hierarchical_landmarks(x[:32], np.asarray(d)[:32], m=32),
        np.arange(32),
    )


# ------------------------------------------------------ dense-budget gate --


def test_dense_budget_refusal_and_auto_regime(monkeypatch):
    n = 64
    x, _ = euler_isometric_swiss_roll(n, seed=0)
    x = jnp.asarray(x)
    monkeypatch.setenv(sparse.ENV_DENSE_BYTES, str(sparse.dense_fit_bytes(n) - 1))
    assert not sparse.dense_budget_ok(n)
    cfg = PipelineConfig(k=8, d=2, block=32)
    with pytest.raises(DenseBudgetError, match="regime"):
        ManifoldPipeline(isomap_stages(), cfg=cfg).run(x)
    # auto regime routes around the refusal
    auto_stages = stages_for(cfg, n)
    assert any(s.name == "sparse_geodesics" for s in auto_stages)
    art = ManifoldPipeline(
        auto_stages, cfg=cfg, name="sparse_isomap"
    ).run(x)
    assert art["embedding"].shape == (n, 2)
    # and with headroom auto stays exact dense
    monkeypatch.setenv(sparse.ENV_DENSE_BYTES, str(sparse.dense_fit_bytes(n)))
    assert all(
        s.name != "sparse_geodesics" for s in stages_for(cfg, n)
    )


# ---------------------------------------------- pipeline, resume, serving --


def _sparse_cfg(m=32):
    return PipelineConfig(k=10, d=2, block=64, regime="sparse", landmarks=m)


def test_sparse_pipeline_resume_mid_landmark_batch(tmp_path, monkeypatch):
    """Kill mid-panel (after 2 of 4 landmark batches), resume: the engine
    re-enters at the recorded batch and the final panel + embedding are
    bit-identical to an uninterrupted run.  Mid-stage checkpoints keep
    the CSR graph + landmark set (segment_requires) because every batch
    relaxes against them."""
    # pin the frontier knobs so m=32 splits into 4 batches of 8
    monkeypatch.setenv("REPRO_FRONTIER_TILES", "8,256,4")
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = _sparse_cfg(32)
    oracle = ManifoldPipeline(
        sparse_isomap_stages(32), cfg=cfg, name="sparse_isomap"
    ).run(x)

    class Boom(Exception):
        pass

    class ExplodingSparse(SparseGeodesicStage):
        def run_segment(self, ctx, art, state, lo, hi):
            if lo >= 2:
                raise Boom()
            return super().run_segment(ctx, art, state, lo, hi)

    def swap(stages, cls):
        return [
            cls() if s.name == "sparse_geodesics" else s for s in stages
        ]

    mgr = CheckpointManager(str(tmp_path), keep=50)
    pipe = ManifoldPipeline(
        swap(sparse_isomap_stages(32), ExplodingSparse),
        cfg=cfg, backend=LocalBackend(segment=1), checkpoint=mgr,
        name="sparse_isomap",
    )
    with pytest.raises(Boom):
        pipe.run(x)
    mgr.wait()
    partial = mgr.read_manifest(mgr.latest_step())
    assert partial["partial"] and partial["segment"] == 2
    assert "_segstate/panel" in partial["keys"]
    # the panel state does NOT subsume the graph: segment_requires keeps it
    assert {"csr_nbr", "csr_w", "lm_idx"} <= set(partial["keys"])

    segs = []

    class TrackingSparse(SparseGeodesicStage):
        def run_segment(self, ctx, art, state, lo, hi):
            segs.append((int(lo), int(hi)))
            return super().run_segment(ctx, art, state, lo, hi)

    mgr2 = CheckpointManager(str(tmp_path), keep=50)
    art = ManifoldPipeline(
        swap(sparse_isomap_stages(32), TrackingSparse),
        cfg=cfg, backend=LocalBackend(segment=1), checkpoint=mgr2,
        name="sparse_isomap",
    ).run(x, resume=True)
    assert segs == [(2, 3), (3, 4)], segs  # only the remaining batches ran
    np.testing.assert_array_equal(
        np.asarray(art["panel"]), np.asarray(oracle["panel"])
    )
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(oracle["embedding"])
    )


def test_landmark_mapper_serves_and_absorbs(tmp_path):
    """Fit sparse, serve from the panel (from_artifacts and
    from_checkpoint agree), absorb arrivals: version bump, base + panel
    columns grown, post-absorb queries finite, geodesics property gone."""
    n, n_new = 192, 24
    x, _ = euler_isometric_swiss_roll(n + n_new, seed=5)
    xb, xs = jnp.asarray(x[:n]), np.asarray(x[n:], np.float32)
    cfg = _sparse_cfg(32)
    mgr = CheckpointManager(str(tmp_path), keep=50)
    art = ManifoldPipeline(
        sparse_isomap_stages(32), cfg=cfg, checkpoint=mgr,
        name="sparse_isomap",
    ).run(xb)
    mapper = LandmarkStreamingMapper.from_artifacts(art, k=10, batch=16)
    mgr.wait()
    restored = LandmarkStreamingMapper.from_checkpoint(mgr, k=10, batch=16)
    y, y_r = np.asarray(mapper(xs)), np.asarray(restored(xs))
    np.testing.assert_array_equal(y, y_r)
    assert np.isfinite(y).all()
    # batching invariance: one chunk vs many
    y_chunked = np.asarray(
        LandmarkStreamingMapper.from_artifacts(art, k=10, batch=7)(xs)
    )
    np.testing.assert_allclose(y_chunked, y, rtol=1e-6, atol=1e-6)
    with pytest.raises(AttributeError, match="panel"):
        mapper.geodesics

    m = int(mapper.lm_idx.shape[0])
    report = mapper.absorb(xs)
    assert report.submitted == n_new and report.absorbed > 0
    assert mapper.version == 1
    assert mapper.n_base == n + report.absorbed
    assert mapper.panel.shape == (m, n + report.absorbed)
    y2 = np.asarray(mapper(xs))
    assert np.isfinite(y2).all()


def test_sparse_residency_no_nn_vars():
    """The jitted sparse path (CSR solve -> panel embed) and the sparse
    absorb expansion carry ZERO (n, n)-shaped jaxpr variables."""
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    )
    from run import _shaped_vars

    from repro.core.update import expand_panel

    n, deg, m, g = 128, 8, 16, 8
    nbr = jnp.zeros((n, deg), jnp.int32)
    w = jnp.full((n, deg), jnp.inf, jnp.float32)
    lm = jnp.arange(m, dtype=jnp.int32)

    def sparse_path(nbr, w, lm):
        panel = sssp_panel(nbr, w, lm)
        return sparse.landmark_mds_general(panel, lm, d=2).embedding

    jx = jax.make_jaxpr(sparse_path)(nbr, w, lm)
    assert _shaped_vars(jx, (n, n)) == 0
    assert _shaped_vars(jx, (m, n)) > 0  # probe sanity: the panel exists

    jx2 = jax.make_jaxpr(expand_panel)(
        jnp.zeros((m, n), jnp.float32),
        jnp.zeros((g, n), jnp.float32),
        jnp.zeros((g, g), jnp.float32),
    )
    for nn in (n, n + g):
        assert _shaped_vars(jx2, (nn, nn)) == 0


def test_landmark_select_stage_rounds_to_backend_multiple():
    """The effective landmark count honours the backend's divisibility
    requirement (folded mesh device count) by rounding down."""

    class FakeBackendCtx:
        class backend:
            landmark_multiple = 8

        class cfg:
            landmarks = 0

    stage = LandmarkSelectStage(30)
    assert stage._effective_m(FakeBackendCtx, 200) == 24
    stage2 = LandmarkSelectStage(None)
    # default_landmarks(200) = 57 -> rounded down to 56
    assert stage2._effective_m(FakeBackendCtx, 200) == 56


# --------------------------------------------------------------- mesh ------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.pipeline import (
    LocalBackend, ManifoldPipeline, MeshBackend, PipelineConfig,
)
from repro.core.sparse import sparse_isomap_stages
from repro.core.streaming import LandmarkStreamingMapper
from repro.data import euler_isometric_swiss_roll
from repro.launch.mesh import make_mesh

n = 256
x, _ = euler_isometric_swiss_roll(n + 32, seed=1)
x = np.pad(x, ((0, 0), (0, 1)))  # 4 features so the model axis divides
xb, xs = x[:n].astype(np.float32), x[n:].astype(np.float32)
cfg = PipelineConfig(k=10, d=2, block=64, regime="sparse", landmarks=64)

art_l = ManifoldPipeline(
    sparse_isomap_stages(64), cfg=cfg, name="sparse_isomap"
).run(jnp.asarray(xb))

mesh = make_mesh((4, 2), ("data", "model"))
mb = MeshBackend(mesh)
xs_sharded = jax.device_put(
    jnp.asarray(xb), NamedSharding(mesh, P("data", "model"))
)
art_m = ManifoldPipeline(
    sparse_isomap_stages(64), cfg=cfg, backend=mb, name="sparse_isomap"
).run(xs_sharded)

np.testing.assert_array_equal(
    np.asarray(art_m["lm_idx"]), np.asarray(art_l["lm_idx"]))
np.testing.assert_array_equal(
    np.asarray(art_m["panel"]), np.asarray(art_l["panel"]))
np.testing.assert_array_equal(
    np.asarray(art_m["embedding"]), np.asarray(art_l["embedding"]))
print("OK mesh-panel-bitmatch")

ml = LandmarkStreamingMapper.from_artifacts(art_l, k=10)
mm = LandmarkStreamingMapper.from_artifacts(art_m, k=10, backend=mb)
np.testing.assert_array_equal(np.asarray(mm(xs)), np.asarray(ml(xs)))
rl, rm = ml.absorb(xs), mm.absorb(xs)
assert rl.absorbed > 0 and rm.absorbed == rl.absorbed
np.testing.assert_array_equal(np.asarray(mm.panel), np.asarray(ml.panel))
np.testing.assert_array_equal(np.asarray(mm(xs)), np.asarray(ml(xs)))
print("OK mesh-sparse-serve-absorb")
print("ALL-MESH-SPARSE-OK")
"""


@pytest.mark.slow
def test_mesh_sparse_suite():
    """The mesh sparse path bit-matches local: landmarks, panel,
    embedding, serving and absorb (zero-collective landmark sharding +
    replicated serving state)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "ALL-MESH-SPARSE-OK" in proc.stdout
