"""Updatable-manifold tests: the border-expansion math (oracle checks,
fusion discipline), the Schoeneman acceptance gate, versioned
publication, update-log resume replay, and checkpoint-secs segment
sizing."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core import apsp, isomap, streaming, update
from repro.core.artifacts import VersionedArtifacts
from repro.core.pipeline import (
    LocalBackend, ManifoldPipeline, PipelineConfig,
)
from repro.core.update import GeodesicUpdater, UpdateConfig
from repro.data import euler_isometric_swiss_roll


# ------------------------------------------------- expansion correctness --


def _random_graph(rng, nn, density=0.12, *, exact=False):
    """Random symmetric weighted graph; ``exact=True`` uses weights that
    are exactly representable with exactly-representable path sums, so
    every computation order yields identical bits."""
    w = rng.integers(1, 64, size=(nn, nn)).astype(np.float32)
    if exact:
        w = w / 8.0                       # small multiples of 2^-3
    else:
        w = w / 7.0
    w = np.minimum(w, w.T)
    mask = rng.random((nn, nn)) < density
    mask = mask | mask.T
    g = np.where(mask, w, np.inf).astype(np.float32)
    np.fill_diagonal(g, 0.0)
    return g


def test_border_expansion_bit_identical_to_from_scratch_apsp():
    """The absorb contract, at full strength: on exact-weight inputs the
    expanded system is bit-identical to a from-scratch blocked
    Floyd-Warshall of the whole augmented graph."""
    rng = np.random.default_rng(0)
    n, m = 48, 8
    g = _random_graph(rng, n + m, exact=True)
    a_base = apsp.apsp_blocked(jnp.asarray(g[:n, :n]), block=16, mode="ref")
    grown = update.expand_geodesics(
        a_base, jnp.asarray(g[n:, :n]), jnp.asarray(g[n:, n:])
    )
    want = apsp.apsp_blocked(jnp.asarray(g), block=28, mode="ref")
    assert np.array_equal(np.asarray(grown), np.asarray(want))


def test_border_expansion_matches_from_scratch_apsp_real_weights():
    """On arbitrary fp32 weights the same equality holds to float
    tolerance (path sums associate differently across schedules)."""
    rng = np.random.default_rng(1)
    n, m = 48, 8
    g = _random_graph(rng, n + m)
    a_base = apsp.apsp_blocked(jnp.asarray(g[:n, :n]), block=16, mode="ref")
    grown = update.expand_geodesics(
        a_base, jnp.asarray(g[n:, :n]), jnp.asarray(g[n:, n:])
    )
    want = apsp.apsp_blocked(jnp.asarray(g), block=28, mode="ref")
    np.testing.assert_allclose(
        np.asarray(grown), np.asarray(want), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("n", [64, 300])
def test_border_expansion_pallas_bit_identical_to_ref(n, rng):
    """Same discipline as every other kernel: the Pallas path (interpret
    mode here) is bit-identical to the jnp oracle composition - also from
    a base with no aligned tiling (n = 300, padded by the kernels)."""
    m = 8
    g = _random_graph(np.random.default_rng(2), n + m)
    a = apsp.apsp_blocked(jnp.asarray(g[:n, :n]), block=n // 2, mode="ref")
    e, f = jnp.asarray(g[n:, :n]), jnp.asarray(g[n:, n:])
    got = update.expand_geodesics(a, e, f, mode="pallas")
    want = update.expand_geodesics(a, e, f, mode="ref")
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_border_expansion_jaxpr_has_no_nn_minplus_intermediate():
    """No (n, n) min-plus product may be materialized by the expansion -
    strictly fewer (n, n)-shaped jaxpr variables than the materializing
    composition (the --only apsp_phase2 discipline)."""
    import benchmarks_path_helper  # noqa: F401  (adds benchmarks/ to path)

    from run import _shaped_vars

    n, m = 128, 16
    a = jnp.zeros((n, n), jnp.float32)
    e = jnp.zeros((m, n), jnp.float32)
    f = jnp.zeros((m, m), jnp.float32)

    def fused():
        return update.expand_geodesics(a, e, f)

    def materializing():
        return update.expand_geodesics_materializing(a, e, f)

    # the materializing oracle is also the value contract
    assert np.array_equal(np.asarray(fused()), np.asarray(materializing()))
    n_fused = _shaped_vars(jax.make_jaxpr(fused)(), (n, n))
    n_mat = _shaped_vars(jax.make_jaxpr(materializing)(), (n, n))
    assert n_fused < n_mat, (n_fused, n_mat)


# ----------------------------------------------------- absorb end-to-end --


@pytest.fixture(scope="module")
def fitted():
    """A fitted base manifold + held-out on-manifold arrivals."""
    x, _ = euler_isometric_swiss_roll(272, seed=0)
    base, new = x[:256], x[256:]
    cfg = isomap.IsomapConfig(k=10, d=2, block=128)
    res = isomap.isomap(jnp.asarray(base), cfg, keep_geodesics=True)
    return np.asarray(base), np.asarray(new), res


def _augmented_oracle(base, accepted, k=10):
    """From-scratch refit of exact Isomap on base ∪ accepted with the
    augmented neighbourhood structure: graph -> APSP -> geodesics."""
    g = update.augmented_graph(base, accepted, k=k)
    return np.asarray(apsp.apsp_blocked(jnp.asarray(g), block=g.shape[0],
                                        mode="ref"))


def test_absorb_matches_refit_on_augmented_graph(fitted):
    """mapper.absorb == refitting exact Isomap on base ∪ accepted (same
    neighbourhood structure) within 1e-5, and the serving version grew."""
    base, new, res = fitted
    mapper = streaming.StreamingMapper(
        jnp.asarray(base), res.geodesics, res.embedding, k=10
    )
    assert mapper.version == 0
    report = mapper.absorb(new)
    assert report.submitted == 16
    assert report.accepted == 16           # on-manifold points all pass
    assert report.absorbed == 16           # local multiple is 1: all flush
    assert mapper.version == 1
    assert mapper.n_base == 272
    want = _augmented_oracle(base, new)
    np.testing.assert_allclose(
        np.asarray(mapper.geodesics), want, rtol=1e-5, atol=1e-5
    )
    # queries now answer from the grown base: a mapper built directly on
    # the refit state agrees (sign-aligned; eigen sign is arbitrary)
    probe, _ = euler_isometric_swiss_roll(300, seed=7)
    probe = jnp.asarray(probe[290:])
    got = np.asarray(mapper(probe))
    from repro.core.centering import double_center
    from repro.core.postprocess import embedding_from_eig
    from repro.core.spectral import power_iteration

    eig = power_iteration(double_center(jnp.square(jnp.asarray(want))),
                          d=2, max_iter=100, tol=1e-9)
    y_refit = embedding_from_eig(eig.eigenvectors, eig.eigenvalues)
    refit_mapper = streaming.StreamingMapper(
        jnp.asarray(np.concatenate([base, new])), jnp.asarray(want),
        y_refit, k=10,
    )
    want_y = np.asarray(refit_mapper(probe))
    sign = np.sign(np.sum(got * want_y, axis=0))
    np.testing.assert_allclose(got, want_y * sign, rtol=1e-4, atol=1e-4)


def test_absorb_gate_rejects_off_manifold_arrivals(fitted):
    """Accepted-vs-rejected gating: on-manifold arrivals pass, far-away
    noise is served-only (never folded into the base)."""
    base, new, res = fitted
    mapper = streaming.StreamingMapper(
        jnp.asarray(base), res.geodesics, res.embedding, k=10
    )
    rng = np.random.default_rng(3)
    noise = rng.normal(0, 60, (8, 3)).astype(np.float32)
    batch = np.concatenate([new[:8], noise])
    report = mapper.absorb(batch)
    assert report.accepted == 8, report.errors
    assert report.rejected == 8
    assert mapper.n_base == 256 + 8
    # the gate scores are ordered as submitted
    assert (report.errors[:8] <= 0.15).all()
    assert (report.errors[8:] > 0.15).all()


def test_absorb_buffers_until_flush_multiple(fitted):
    """Accepted arrivals below the flush multiple stay buffered (no
    version bump) and fold in once the group completes."""
    base, new, res = fitted
    mapper = streaming.StreamingMapper(
        jnp.asarray(base), res.geodesics, res.embedding, k=10,
        update=UpdateConfig(multiple=8),
    )
    r1 = mapper.absorb(new[:5])
    assert (r1.accepted, r1.absorbed, r1.buffered) == (5, 0, 5)
    assert mapper.version == 0 and mapper.n_base == 256
    r2 = mapper.absorb(new[5:12])
    assert (r2.accepted, r2.absorbed, r2.buffered) == (7, 8, 4)
    assert mapper.version == 1 and mapper.n_base == 264
    # the flushed prefix is the first 8 accepted points, in order
    np.testing.assert_array_equal(
        np.asarray(mapper.x_base)[256:], new[:8]
    )


def test_absorb_empty_batch_is_a_noop(fitted):
    base, _, res = fitted
    mapper = streaming.StreamingMapper(
        jnp.asarray(base), res.geodesics, res.embedding, k=10
    )
    report = mapper.absorb(np.zeros((0, 3), np.float32))
    assert report.submitted == 0 and report.absorbed == 0
    assert mapper.version == 0


def test_versioned_artifacts_publish_is_atomic():
    """Readers holding a snapshot keep it across a publish; the store
    seeds version 0 from the pipeline's exported artifacts."""
    store = VersionedArtifacts({"a": 1, "b": 2})
    before = store.current
    assert (before.version, before["a"]) == (0, 1)
    after = store.publish({"a": 10})
    assert (after.version, after["a"], after["b"]) == (1, 10, 2)
    # the captured snapshot is untouched
    assert (before.version, before["a"]) == (0, 1)
    assert store.current is after


def test_artifact_store_versioned_snapshot():
    from repro.core.artifacts import ArtifactStore

    store = ArtifactStore()
    store.put("x", 1, producer="input")
    store.put("embedding", 2, producer="eigen")
    versions = store.versioned(["x", "embedding"])
    assert versions.current["embedding"] == 2
    with pytest.raises(KeyError, match="geodesics"):
        store.versioned(["geodesics"])


def test_absorb_old_snapshot_keeps_serving(fitted):
    """A reader that captured the pre-absorb snapshot still serves
    consistent version-0 state after the absorb lands."""
    base, new, res = fitted
    mapper = streaming.StreamingMapper(
        jnp.asarray(base), res.geodesics, res.embedding, k=10
    )
    snap0 = mapper.snapshot()
    y_before = np.asarray(mapper._map_batch(jnp.asarray(new), snap0))
    mapper.absorb(new)
    y_after_old_snap = np.asarray(mapper._map_batch(jnp.asarray(new), snap0))
    np.testing.assert_array_equal(y_before, y_after_old_snap)
    assert snap0["x"].shape[0] == 256
    assert mapper.snapshot()["x"].shape[0] == 272


# ------------------------------------------------------ update-log resume --


def test_resume_replays_update_log(fitted, tmp_path):
    """A restored server replays absorbed points (original flush
    grouping) instead of losing them - bit-identical grown state."""
    base, new, _ = fitted
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    m1 = streaming.StreamingMapper.from_artifacts(
        art, k=10,
        update=UpdateConfig(log_dir=str(tmp_path / "updates")),
    )
    m1.absorb(new[:6])
    m1.absorb(new[6:])
    assert m1.version == 2
    m2 = streaming.StreamingMapper.from_checkpoint(
        CheckpointManager(str(tmp_path), keep=10), k=10
    )
    assert m2.version == 2
    assert m2.n_base == m1.n_base == 272
    assert np.array_equal(np.asarray(m1.geodesics),
                          np.asarray(m2.geodesics))
    assert np.array_equal(np.asarray(m1.embedding),
                          np.asarray(m2.embedding))
    # the restored mapper keeps appending to the same log
    r = m2.absorb(np.asarray(base[:2]) + 1e-4)
    assert m2.version == 3
    log = GeodesicUpdater.find_log(str(tmp_path))
    assert log is not None
    x_all, flushes, manifest = log
    assert x_all.shape[0] == 16 + r.accepted
    assert flushes[:2] == [6, 10]
    assert manifest["k"] == 10 and manifest["n_base0"] == 256


def test_resume_without_update_log_serves_base(fitted, tmp_path):
    base, new, _ = fitted
    mgr = CheckpointManager(str(tmp_path), keep=10)
    ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    mapper = streaming.StreamingMapper.from_checkpoint(
        CheckpointManager(str(tmp_path), keep=10), k=10
    )
    assert mapper.version == 0 and mapper.n_base == 256


def test_resume_rejects_incompatible_update_log(fitted, tmp_path):
    """A log absorbed under different identity params (k) must not be
    silently replayed onto this fit - same fingerprint discipline as
    pipeline resume."""
    base, new, _ = fitted
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    m1 = streaming.StreamingMapper.from_artifacts(
        art, k=10, update=UpdateConfig(log_dir=str(tmp_path / "updates")),
    )
    m1.absorb(new)
    with pytest.raises(ValueError, match="absorbed\\s+against k=10"):
        streaming.StreamingMapper.from_checkpoint(
            CheckpointManager(str(tmp_path), keep=10), k=12
        )


def test_replay_preserves_recorded_flush_grouping(fitted, tmp_path):
    """Replay applies the *recorded* groups verbatim even when the
    restoring updater's flush multiple would have grouped differently."""
    base, new, _ = fitted
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    m1 = streaming.StreamingMapper.from_artifacts(
        art, k=10, update=UpdateConfig(log_dir=str(tmp_path / "updates")),
    )
    m1.absorb(new[:6])                 # multiple=1: one flush of 6
    m1.absorb(new[6:])                 # one flush of 10
    # restore with a multiple that does NOT divide the recorded groups
    m2 = streaming.StreamingMapper.from_checkpoint(
        CheckpointManager(str(tmp_path), keep=10), k=10,
        update=UpdateConfig(multiple=4),
    )
    assert m2.version == 2 and m2.n_base == 272
    assert np.array_equal(np.asarray(m1.geodesics),
                          np.asarray(m2.geodesics))


def test_update_log_steps_stay_monotonic_across_fresh_runs(fitted,
                                                           tmp_path):
    """A fresh (non-resume) server reusing a checkpoint dir must write
    its log *above* the stale one, so retention GC keeps the new entries
    and find_log returns them."""
    base, new, _ = fitted
    cfg = UpdateConfig(log_dir=str(tmp_path / "updates"))
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    m1 = streaming.StreamingMapper.from_artifacts(art, k=10, update=cfg)
    m1.absorb(new[:6])
    m1.absorb(new[6:10])
    # fresh server, same dir, absorbs different points from scratch
    m2 = streaming.StreamingMapper.from_artifacts(art, k=10, update=cfg)
    m2.absorb(new[10:])
    log = GeodesicUpdater.find_log(str(tmp_path))
    assert log is not None
    x_all, flushes, _ = log
    assert flushes == [6]              # the NEW run's log is newest
    np.testing.assert_array_equal(x_all, new[10:])


def test_update_log_buffered_tail_survives_restart(fitted, tmp_path):
    """Accepted-but-unflushed arrivals are in the log too: the restored
    updater re-buffers them so the next flush group completes."""
    base, new, _ = fitted
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    cfg = UpdateConfig(multiple=8, log_dir=str(tmp_path / "updates"))
    m1 = streaming.StreamingMapper.from_artifacts(art, k=10, update=cfg)
    m1.absorb(new[:5])                     # buffered, below the multiple
    assert m1.version == 0
    m2 = streaming.StreamingMapper.from_checkpoint(
        CheckpointManager(str(tmp_path), keep=10), k=10,
        update=UpdateConfig(multiple=8),
    )
    assert m2.version == 0 and m2.n_base == 256
    r = m2.absorb(new[5:12])               # completes the group of 8
    assert r.absorbed == 8
    np.testing.assert_array_equal(np.asarray(m2.x_base)[256:], new[:8])


# ------------------------------------------- checkpoint-secs segmenting --


class _TickingStage:
    """ResumableStage whose units 'take' a scripted wall time (the test
    monkeypatches the engine's clock)."""

    name = "apsp"                 # reuse a registered chain position
    requires = ("graph",)
    provides = ("geodesics_raw",)
    segment_requires = ()

    def __init__(self):
        self.segments = []        # [(lo, hi)]

    def num_units(self, ctx, art):
        return 8

    def init_state(self, ctx, art):
        return {"g": art["graph"]}

    def run_segment(self, ctx, art, state, lo, hi):
        self.segments.append((int(lo), int(hi)))
        return state

    def finalize(self, ctx, art, state):
        return {"geodesics_raw": state["g"]}


def test_checkpoint_secs_derives_segment_from_measured_unit(monkeypatch):
    """checkpoint_secs=4 with a measured 1s/unit panel must yield 4-unit
    segments after the (untimed, compile-absorbing) warm unit and the
    timed calibration unit."""
    import repro.core.pipeline as pipeline_mod

    from repro.core.pipeline import (
        ClampStage, GraphStage, KNNStage, ManifoldPipeline,
    )

    ticks = iter(range(1000))     # perf_counter: +1.0s per call

    class _Clock:
        @staticmethod
        def perf_counter():
            return float(next(ticks))

    monkeypatch.setattr(pipeline_mod, "time", _Clock)
    stage = _TickingStage()
    x, _ = euler_isometric_swiss_roll(64, seed=0)
    pipe = ManifoldPipeline(
        stages=[KNNStage(), GraphStage(), stage, ClampStage()],
        cfg=PipelineConfig(k=5, d=2, block=32),
        backend=LocalBackend(checkpoint_secs=4.0),
        exports=["geodesics"],
    )
    pipe.run(jnp.asarray(x))
    # unit 0 warms (untimed - it would include jit compile), unit 1
    # calibrates (1 tick = 1s/unit), then 4-unit segments
    assert stage.segments == [(0, 1), (1, 2), (2, 6), (6, 8)]


def test_checkpoint_secs_ignored_when_segment_explicit():
    stage = _TickingStage()
    from repro.core.pipeline import (
        ClampStage, GraphStage, KNNStage, ManifoldPipeline,
    )

    x, _ = euler_isometric_swiss_roll(64, seed=0)
    pipe = ManifoldPipeline(
        stages=[KNNStage(), GraphStage(), stage, ClampStage()],
        cfg=PipelineConfig(k=5, d=2, block=32),
        backend=LocalBackend(segment=3, checkpoint_secs=100.0),
        exports=["geodesics"],
    )
    pipe.run(jnp.asarray(x))
    assert stage.segments == [(0, 3), (3, 6), (6, 8)]
