"""ManifoldPipeline engine tests: fused min-plus-update kernel oracles,
stage-graph execution/validation, stage-boundary checkpoint resume, and
streaming new-point mapping vs a full-batch Isomap oracle."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core import apsp, graph, isomap, knn, metrics, streaming
from repro.core.pipeline import (
    GraphStage,
    KNNStage,
    LocalBackend,
    ManifoldPipeline,
    PipelineConfig,
    isomap_stages,
    lle_stages,
)
from repro.core.postprocess import clamp_disconnected, embedding_from_eig
from repro.data import euler_isometric_swiss_roll
from repro.kernels import ops, ref
from repro.kernels.minplus_update import minplus_update as mpu_pallas


# ------------------------------------------------ fused min-plus update ---


@pytest.mark.parametrize(
    "m,k,n", [(8, 8, 8), (32, 32, 32), (64, 32, 96), (128, 64, 128)]
)
def test_minplus_update_ref_bit_identical_to_unfused(m, k, n, rng):
    g = rng.uniform(0, 30, (m, n)).astype(np.float32)
    c = rng.uniform(0, 10, (m, k)).astype(np.float32)
    r = rng.uniform(0, 10, (k, n)).astype(np.float32)
    c[c < 2.0] = np.inf  # exercise the +inf (no-edge) path
    fused = np.asarray(ops.minplus_update(g, c, r, mode="ref"))
    unfused = np.minimum(g, np.asarray(ops.minplus(c, r, mode="ref")))
    # min is exact in fp: the fused accumulation must be bit-identical
    np.testing.assert_array_equal(fused, unfused)


@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk,unroll",
    [
        (32, 32, 32, 32, 32, 32, 4),
        (64, 64, 64, 32, 32, 32, 8),
        (128, 64, 96, 64, 32, 64, 8),
        (8, 8, 8, 8, 8, 8, 1),
        # register sub-tiles at their edges (kernels/minplus.subtile)
        (16, 128, 2048, 8, 1024, 128, 8),
        (48, 64, 384, 24, 384, 64, 8),
        (24, 256, 256, 24, 128, 128, 8),
    ],
)
def test_minplus_update_pallas_matches_oracle(m, k, n, bm, bn, bk, unroll, rng):
    g = rng.uniform(0, 30, (m, n)).astype(np.float32)
    c = rng.uniform(0, 10, (m, k)).astype(np.float32)
    r = rng.uniform(0, 10, (k, n)).astype(np.float32)
    c[c < 1.0] = np.inf  # no-edge entries ride through every sub-tile
    want = np.minimum(g, np.min(c[:, :, None] + r[None, :, :], axis=1))
    got = mpu_pallas(
        g, c, r, bm=bm, bn=bn, bk=bk, unroll=unroll, interpret=True
    )
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(ref.minplus_update_ref(g, c, r), want)
    np.testing.assert_array_equal(got, ref.minplus_update_ref(g, c, r))


def test_apsp_fused_geodesics_bit_identical_to_unfused(rng):
    """apsp_blocked (fused Phase 3) vs a hand-unfused reimplementation:
    geodesics must be bit-identical in mode='ref'."""
    import functools
    import jax

    x, _ = euler_isometric_swiss_roll(256, seed=0)
    d, i = knn.knn_blocked(jnp.asarray(x), k=10, block=128)
    g = graph.knn_to_graph(d, i, n=256)

    @functools.partial(jax.jit, static_argnames=("block",))
    def apsp_unfused(g, block):
        n = g.shape[0]
        q = n // block

        def iteration(i, g):
            off = i * block
            dd = jax.lax.dynamic_slice(g, (off, off), (block, block))
            dd = ops.floyd_warshall(dd, mode="ref")
            r = jax.lax.dynamic_slice(g, (off, 0), (block, n))
            c = jax.lax.dynamic_slice(g, (0, off), (n, block))
            r = ops.minplus(dd, r, mode="ref")
            c = ops.minplus(c, dd, mode="ref")
            return jnp.minimum(g, ops.minplus(c, r, mode="ref"))

        return jax.lax.fori_loop(0, q, iteration, g)

    a_fused = apsp.apsp_blocked(g, block=64, mode="ref")
    a_unfused = apsp_unfused(g, 64)
    np.testing.assert_array_equal(np.asarray(a_fused), np.asarray(a_unfused))


# -------------------------------------------------- shared postprocess ----


def test_clamp_disconnected():
    a = jnp.asarray(
        [[0.0, 1.0, np.inf], [1.0, 0.0, 2.0], [np.inf, 2.0, 0.0]],
        jnp.float32,
    )
    out = np.asarray(clamp_disconnected(a))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[0, 2], 2.2, rtol=1e-6)  # 1.1 * diameter
    # finite entries untouched
    np.testing.assert_array_equal(out[1], np.asarray(a[1]))


def test_embedding_from_eig_clamps_negative():
    q = jnp.asarray([[1.0, 1.0], [2.0, 2.0]], jnp.float32)
    lam = jnp.asarray([4.0, -1.0], jnp.float32)
    y = np.asarray(embedding_from_eig(q, lam))
    np.testing.assert_allclose(y[:, 0], [2.0, 4.0], rtol=1e-6)
    np.testing.assert_array_equal(y[:, 1], [0.0, 0.0])  # not NaN


# ----------------------------------------------------- pipeline engine ----


def test_pipeline_artifacts_and_driver_parity():
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = isomap.IsomapConfig(k=10, d=2, block=128)
    pipe = ManifoldPipeline(cfg=cfg.to_pipeline())
    art = pipe.run(x)
    # exported artifacts survive the run...
    for key in ("x", "geodesics", "embedding", "eigenvalues", "iterations"):
        assert key in art, key
    # ...consumed intermediates are dropped when their last consumer runs
    for key in ("knn_dists", "knn_idx", "graph", "geodesics_raw", "gram"):
        assert key not in art, key
    assert set(art) == set(pipe.exports)
    assert art.exports == pipe.exports  # stamped on the returned store
    # lifecycle metadata: every artifact knows its producing stage
    assert art.record("geodesics").producer == "clamp"
    assert art.record("embedding").producer == "eigen"
    assert art.record("x").producer == "input"
    res = isomap.isomap(x, cfg)
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(res.embedding)
    )


def test_pipeline_exports_override_keeps_intermediates():
    """An explicit exports list overrides the stage-declared defaults -
    here keeping the gram matrix alive through the end of the run."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    cfg = PipelineConfig(k=10, d=2, block=128)
    art = ManifoldPipeline(
        cfg=cfg, exports=("gram", "embedding", "geodesics")
    ).run(jnp.asarray(x))
    assert "gram" in art
    assert "graph" not in art  # still pruned: nobody exported it


def test_pipeline_rejects_unknown_exports():
    with pytest.raises(ValueError, match="exports"):
        ManifoldPipeline(exports=("not_an_artifact",))


def test_pipeline_validates_stage_graph():
    with pytest.raises(ValueError, match="requires"):
        ManifoldPipeline([GraphStage()])  # knn_dists/knn_idx missing
    with pytest.raises(ValueError, match="duplicate"):
        ManifoldPipeline([KNNStage(), KNNStage()])
    # well-formed graphs validate
    ManifoldPipeline(isomap_stages())
    ManifoldPipeline(lle_stages())


def test_pipeline_resume_round_trip(tmp_path):
    """Kill-and-restart: a resumed pipeline restores the stage-boundary
    artifacts and skips every completed stage, bit-identically."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = PipelineConfig(k=10, d=2, block=128)

    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(cfg=cfg, checkpoint=mgr).run(x)
    steps = mgr.all_steps()
    assert len(steps) == 6  # one resume point per stage
    assert mgr.read_manifest(steps[-1])["stage"] == "eigen"
    assert mgr.read_manifest(steps[-1])["pipeline"] == "isomap"

    class Exploder:
        """Stage that must never run: resume skips everything before it."""

        name = "knn"
        requires = ("x",)
        provides = ("knn_dists", "knn_idx")

        def run(self, ctx, a):
            raise AssertionError("resumed pipeline re-ran a finished stage")

    stages = [Exploder()] + isomap_stages()[1:]
    mgr2 = CheckpointManager(str(tmp_path), keep=10)
    art2 = ManifoldPipeline(stages, cfg=cfg, checkpoint=mgr2).run(
        x, resume=True
    )
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(art2["embedding"])
    )
    np.testing.assert_array_equal(
        np.asarray(art["geodesics"]), np.asarray(art2["geodesics"])
    )


def test_pipeline_resume_from_mid_stage(tmp_path):
    """Resume from a partial run (checkpoints only up to apsp) re-runs
    exactly the remaining stages."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = PipelineConfig(k=10, d=2, block=128)

    front = isomap_stages()[:3]  # knn, graph, apsp
    mgr = CheckpointManager(str(tmp_path), keep=10)
    ManifoldPipeline(front, cfg=cfg, checkpoint=mgr).run(x)
    assert mgr.read_manifest(mgr.latest_step())["stage"] == "apsp"

    ran = []

    class Tracker:
        def __init__(self, inner):
            self.inner = inner
            self.name = inner.name
            self.requires = inner.requires
            self.provides = inner.provides

        def run(self, ctx, a):
            ran.append(self.name)
            return self.inner.run(ctx, a)

    mgr2 = CheckpointManager(str(tmp_path), keep=10)
    stages = [Tracker(s) for s in isomap_stages()]
    art = ManifoldPipeline(stages, cfg=cfg, checkpoint=mgr2).run(
        x, resume=True
    )
    assert ran == ["clamp", "center", "eigen"], ran
    oracle = ManifoldPipeline(cfg=cfg).run(x)
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(oracle["embedding"])
    )


def test_pipeline_resume_rejects_config_mismatch(tmp_path):
    """A checkpoint written under a different config must not be resumed
    (a k=10 geodesic matrix is not a k=15 answer)."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    mgr = CheckpointManager(str(tmp_path), keep=10)
    ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(x)

    ran = []

    class Tracker(KNNStage):
        def run(self, ctx, a):
            ran.append(self.name)
            return super().run(ctx, a)

    stages = [Tracker()] + isomap_stages()[1:]
    mgr2 = CheckpointManager(str(tmp_path), keep=10)
    ManifoldPipeline(
        stages, cfg=PipelineConfig(k=15, d=2, block=128), checkpoint=mgr2
    ).run(x, resume=True)
    assert ran == ["knn"]  # full re-run, nothing resumed


def test_pipeline_resume_rejects_input_shape_mismatch(tmp_path):
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    mgr = CheckpointManager(str(tmp_path), keep=10)
    cfg = PipelineConfig(k=10, d=2, block=128)
    ManifoldPipeline(cfg=cfg, checkpoint=mgr).run(jnp.asarray(x))
    with pytest.raises(ValueError, match="checkpointed input"):
        ManifoldPipeline(cfg=cfg, checkpoint=mgr).run(
            jnp.asarray(x[:128]), resume=True
        )


def test_pipeline_resume_falls_back_past_filtered_checkpoints(tmp_path):
    """checkpoint_artifacts may drop artifacts later stages require; the
    resume scan must fall back to a boundary whose saved keys satisfy the
    remaining `requires` chain instead of KeyError-ing."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = PipelineConfig(k=10, d=2, block=128)
    mgr = CheckpointManager(str(tmp_path), keep=10)
    # knn+graph only, and the checkpoints keep none of the artifacts the
    # downstream stages need (only x is implicitly retained)
    ManifoldPipeline(
        isomap_stages()[:2], cfg=cfg, checkpoint=mgr,
        checkpoint_artifacts=(),
    ).run(x)
    assert mgr.read_manifest(mgr.latest_step())["stage"] == "graph"

    ran = []

    class Tracker:
        def __init__(self, inner):
            self.inner = inner
            self.name = inner.name
            self.requires = inner.requires
            self.provides = inner.provides

        def run(self, ctx, a):
            ran.append(self.name)
            return self.inner.run(ctx, a)

    mgr2 = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        [Tracker(s) for s in isomap_stages()], cfg=cfg, checkpoint=mgr2
    ).run(x, resume=True)
    # no usable boundary -> clean full re-run, correct result
    assert ran == [s.name for s in isomap_stages()], ran
    oracle = ManifoldPipeline(cfg=cfg).run(x)
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(oracle["embedding"])
    )


# ------------------------------------------- artifact lifecycle engine ----


class _Tracker:
    """Transparent stage wrapper recording which stages (re-)ran."""

    def __init__(self, inner, log):
        self.inner = inner
        self.log = log
        self.name = inner.name
        self.requires = inner.requires
        self.provides = inner.provides
        for attr in ("exports", "segment_requires"):
            if hasattr(inner, attr):
                setattr(self, attr, getattr(inner, attr))

    def run(self, ctx, a):
        self.log.append(self.name)
        return self.inner.run(ctx, a)


def test_checkpoints_persist_only_live_artifacts(tmp_path):
    """Acceptance: the boundary written after `eigen` holds only exported
    artifacts - no graph/geodesics_raw/gram - and every earlier boundary
    has already dropped the intermediates its remaining stages no longer
    need (payloads are O(n^2), not O(stages * n^2))."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    mgr = CheckpointManager(str(tmp_path), keep=20)
    pipe = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    )
    pipe.run(jnp.asarray(x))
    by_stage = {
        mgr.read_manifest(s)["stage"]: set(mgr.read_manifest(s)["keys"])
        for s in mgr.all_steps()
    }
    assert by_stage["eigen"] & {"graph", "geodesics_raw", "gram"} == set()
    assert {"x", "geodesics", "embedding"} <= by_stage["eigen"]
    # graph is consumed by apsp: gone from the apsp boundary onward
    assert "graph" in by_stage["graph"]
    assert "graph" not in by_stage["apsp"]
    # geodesics_raw is consumed by clamp: gone from the clamp boundary
    assert "geodesics_raw" in by_stage["apsp"]
    assert "geodesics_raw" not in by_stage["clamp"]
    # gram is consumed by eigen: alive only at the center boundary
    assert "gram" in by_stage["center"]
    assert "gram" not in by_stage["eigen"]
    # placements + producers recorded for every persisted artifact
    final = mgr.read_manifest(mgr.all_steps()[-1])
    assert set(final["placements"]) == set(final["keys"])
    assert final["producers"]["geodesics"] == "clamp"


def test_resume_scan_falls_back_when_pruning_invalidates_newest(tmp_path):
    """Satellite: checkpoint_artifacts filtering + liveness pruning can
    make the newest boundary unsatisfiable for a longer stage chain; the
    scan must fall back to an older step that still holds what the
    remaining stages require - not KeyError, not a full re-run."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = PipelineConfig(k=10, d=2, block=128)
    mgr = CheckpointManager(str(tmp_path), keep=20)
    ManifoldPipeline(cfg=cfg, checkpoint=mgr).run(x)
    # newest boundary (eigen) dropped gram; an extended pipeline with an
    # extra stage consuming gram cannot resume there
    assert "gram" not in mgr.read_manifest(mgr.all_steps()[-1])["keys"]

    class GramNorm:
        name = "gram_norm"
        requires = ("gram",)
        provides = ("gram_norm",)

        def run(self, ctx, a):
            return {"gram_norm": jnp.linalg.norm(a["gram"])}

    ran = []
    stages = [_Tracker(s, ran) for s in isomap_stages()] + [GramNorm()]
    mgr2 = CheckpointManager(str(tmp_path), keep=20)
    art = ManifoldPipeline(stages, cfg=cfg, checkpoint=mgr2).run(
        x, resume=True
    )
    # fell back to the center boundary (gram still live there): only
    # eigen re-ran before the new tail stage
    assert ran == ["eigen"], ran
    assert "gram_norm" in art
    oracle = ManifoldPipeline(cfg=cfg).run(x)
    np.testing.assert_allclose(
        np.asarray(art["embedding"]), np.asarray(oracle["embedding"]),
        rtol=1e-5, atol=1e-6,
    )


def test_segmented_apsp_checkpoint_and_mid_stage_resume(tmp_path):
    """Kill mid-APSP (after 2 of 4 diagonal panels), resume: the engine
    re-enters the stage at the recorded panel and the final geodesics are
    bit-identical to an uninterrupted run.  The mid-stage checkpoint
    holds ONE O(n^2) array (the evolving state subsumes the graph)."""
    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = PipelineConfig(k=10, d=2, block=64)  # q = 4 panels
    oracle = ManifoldPipeline(cfg=cfg).run(x)

    class Boom(Exception):
        pass

    from repro.core.pipeline import APSPStage

    class ExplodingAPSP(APSPStage):
        def run_segment(self, ctx, art, state, lo, hi):
            if lo >= 2:
                raise Boom()
            return super().run_segment(ctx, art, state, lo, hi)

    mgr = CheckpointManager(str(tmp_path), keep=50)
    stages = [
        s if s.name != "apsp" else ExplodingAPSP() for s in isomap_stages()
    ]
    pipe = ManifoldPipeline(
        stages, cfg=cfg, backend=LocalBackend(segment=1), checkpoint=mgr
    )
    with pytest.raises(Boom):
        pipe.run(x)
    mgr.wait()
    partial = mgr.read_manifest(mgr.latest_step())
    assert partial["partial"] and partial["segment"] == 2
    assert "_segstate/g" in partial["keys"]
    assert "graph" not in partial["keys"]  # state subsumes the input

    segs = []

    class TrackingAPSP(APSPStage):
        def run_segment(self, ctx, art, state, lo, hi):
            segs.append((int(lo), int(hi)))
            return super().run_segment(ctx, art, state, lo, hi)

    stages2 = [
        s if s.name != "apsp" else TrackingAPSP() for s in isomap_stages()
    ]
    mgr2 = CheckpointManager(str(tmp_path), keep=50)
    art = ManifoldPipeline(
        stages2, cfg=cfg, backend=LocalBackend(segment=1), checkpoint=mgr2
    ).run(x, resume=True)
    assert segs == [(2, 3), (3, 4)], segs  # only the remaining panels ran
    np.testing.assert_array_equal(
        np.asarray(art["geodesics"]), np.asarray(oracle["geodesics"])
    )
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(oracle["embedding"])
    )


def test_landmark_mid_sweep_checkpoint_and_resume(tmp_path):
    """The landmark Bellman-Ford tail checkpoints mid-sweep through the
    same ResumableStage protocol; its segment checkpoints keep the graph
    (segment_requires) because every sweep relaxes against it."""
    from repro.core.isomap import LandmarkStage

    x, _ = euler_isometric_swiss_roll(256, seed=1)
    x = jnp.asarray(x)
    cfg = PipelineConfig(k=10, d=2)
    oracle = ManifoldPipeline(
        [KNNStage(), GraphStage(), LandmarkStage(32)],
        cfg=cfg, name="landmark_isomap",
    ).run(x)

    class Boom(Exception):
        pass

    class ExplodingLandmark(LandmarkStage):
        def run_segment(self, ctx, art, state, lo, hi):
            if lo >= 16:
                raise Boom()
            return super().run_segment(ctx, art, state, lo, hi)

    mgr = CheckpointManager(str(tmp_path), keep=50)
    pipe = ManifoldPipeline(
        [KNNStage(), GraphStage(), ExplodingLandmark(32, segment=8)],
        cfg=cfg, checkpoint=mgr, name="landmark_isomap",
    )
    with pytest.raises(Boom):
        pipe.run(x)
    mgr.wait()
    partial = mgr.read_manifest(mgr.latest_step())
    assert partial["partial"] and partial["segment"] == 16
    assert {"_segstate/dl", "graph"} <= set(partial["keys"])

    segs = []

    class TrackingLandmark(LandmarkStage):
        def run_segment(self, ctx, art, state, lo, hi):
            segs.append((int(lo), int(hi)))
            return super().run_segment(ctx, art, state, lo, hi)

    mgr2 = CheckpointManager(str(tmp_path), keep=50)
    art = ManifoldPipeline(
        [KNNStage(), GraphStage(), TrackingLandmark(32, segment=8)],
        cfg=cfg, checkpoint=mgr2, name="landmark_isomap",
    ).run(x, resume=True)
    assert segs == [(16, 24), (24, 32)], segs
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(oracle["embedding"])
    )

    # stage-identity params are part of resume compatibility: a pipeline
    # asking for a DIFFERENT landmark count must not adopt the m=32
    # checkpoints (neither the mid-sweep state nor the graph boundary is
    # wrong for it, but the landmark stage params changed)
    segs16 = []

    class Tracking16(TrackingLandmark):
        def run_segment(self, ctx, art, state, lo, hi):
            segs16.append((int(lo), int(hi)))
            return LandmarkStage.run_segment(self, ctx, art, state, lo, hi)

    mgr3 = CheckpointManager(str(tmp_path), keep=50)
    art16 = ManifoldPipeline(
        [KNNStage(), GraphStage(), Tracking16(16, segment=8)],
        cfg=cfg, checkpoint=mgr3, name="landmark_isomap",
    ).run(x, resume=True)
    # resumed from the graph boundary (landmark params unchanged there),
    # then ran the full 32-sweep landmark tail with m=16 from scratch
    assert segs16 == [(0, 8), (8, 16), (16, 24), (24, 32)], segs16
    assert art16["landmark_embedding"].shape[0] == 16


def test_all_steps_tolerates_malformed_entries(tmp_path):
    """Satellite: a stray step_foo file/dir in the checkpoint directory
    must not kill every resume scan with ValueError from int() - and a
    manual step_0000000003_backup copy must neither alias step 3 nor
    become a phantom latest_step."""
    mgr = CheckpointManager(str(tmp_path), keep=10)
    mgr.save(3, {"x": jnp.zeros((2,))}, blocking=True)
    (tmp_path / "step_foo").write_text("not a checkpoint")
    (tmp_path / "step_").mkdir()
    (tmp_path / "step_0000000003_backup").mkdir()
    (tmp_path / "step_5_old").mkdir()
    (tmp_path / "unrelated.txt").write_text("")
    assert mgr.all_steps() == [3]
    assert mgr.latest_step() == 3
    # and the pipeline resume scan over such a directory still works
    x, _ = euler_isometric_swiss_roll(128, seed=1)
    cfg = PipelineConfig(k=10, d=2, block=64)
    ManifoldPipeline(cfg=cfg, checkpoint=mgr).run(
        jnp.asarray(x), resume=True
    )


# ----------------------------------------------------------- streaming ----


@pytest.fixture(scope="module")
def stream_setup():
    x, latent = euler_isometric_swiss_roll(768, seed=3)
    base, held_out = x[:640], x[640:]
    cfg = isomap.IsomapConfig(k=10, d=2, block=128)
    res_base = isomap.isomap(jnp.asarray(base), cfg, keep_geodesics=True)
    res_full = isomap.isomap(jnp.asarray(x), cfg)
    return x, latent, base, held_out, res_base, res_full


def test_streaming_matches_full_batch_oracle(stream_setup):
    """Held-out points mapped through the streaming path must land within
    tolerance of where a full-batch Isomap (the oracle) puts them."""
    x, latent, base, held_out, res_base, res_full = stream_setup
    y_new = streaming.map_new_points(
        jnp.asarray(held_out), jnp.asarray(base),
        res_base.geodesics, res_base.embedding, k=10,
    )
    stream_full = np.concatenate([np.asarray(res_base.embedding),
                                  np.asarray(y_new)])
    # compare the two embeddings of the SAME points (procrustes aligns the
    # arbitrary rotation/reflection/scale between the runs)
    err = float(metrics.procrustes_error(
        jnp.asarray(stream_full), res_full.embedding
    ))
    assert err < 5e-3, err
    # and both must reconstruct the latent chart
    err_latent = float(metrics.procrustes_error(
        jnp.asarray(stream_full), jnp.asarray(latent)
    ))
    assert err_latent < 0.02, err_latent


def test_streaming_mapper_batching_invariance(stream_setup):
    x, latent, base, held_out, res_base, _ = stream_setup
    mapper = streaming.StreamingMapper(
        jnp.asarray(base), res_base.geodesics, res_base.embedding,
        k=10, batch=32,
    )
    y_batched = np.asarray(mapper(jnp.asarray(held_out)))  # 128 pts, 4 batches
    y_once = np.asarray(streaming.map_new_points(
        jnp.asarray(held_out), jnp.asarray(base),
        res_base.geodesics, res_base.embedding, k=10,
    ))
    np.testing.assert_allclose(y_batched, y_once, rtol=1e-5, atol=1e-6)


def test_streaming_mapper_from_checkpoint(tmp_path):
    """Pipeline artifacts persisted at a stage boundary are sufficient to
    serve streaming queries after a restart (no refit)."""
    x, _ = euler_isometric_swiss_roll(320, seed=3)
    base, new = x[:256], x[256:]
    mgr = CheckpointManager(str(tmp_path), keep=10)
    pipe = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    )
    art = pipe.run(jnp.asarray(base))
    y_live = np.asarray(
        streaming.StreamingMapper.from_artifacts(art, k=10)(jnp.asarray(new))
    )

    mgr2 = CheckpointManager(str(tmp_path), keep=10)
    mapper = streaming.StreamingMapper.from_checkpoint(mgr2, k=10)
    y_restored = np.asarray(mapper(jnp.asarray(new)))
    np.testing.assert_allclose(y_restored, y_live, rtol=1e-5, atol=1e-6)


def test_streaming_mapper_from_checkpoint_missing(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        streaming.StreamingMapper.from_checkpoint(mgr)


# --------------------------------------------------------- serve driver ---


def test_serve_manifold_smoke(tmp_path):
    from repro.launch.serve import serve_manifold

    out = serve_manifold(
        n_base=512, n_stream=64, stream_batch=32, block=128,
        checkpoint_dir=str(tmp_path),
    )
    assert out["procrustes_error"] < 0.02, out
    # artifacts persisted: a resumed serve skips the fit
    out2 = serve_manifold(
        n_base=512, n_stream=64, stream_batch=32, block=128,
        checkpoint_dir=str(tmp_path), resume=True,
    )
    assert out2["procrustes_error"] == pytest.approx(
        out["procrustes_error"], rel=1e-5
    )
