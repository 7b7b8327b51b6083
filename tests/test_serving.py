"""Serving-surface + streaming-hardening tests: the batched request queue
scheduler, the tolerant checkpoint scan, the degenerate-eigenvalue and
degenerate-graph clamps, StreamingMapper edge cases, and the serve CLI's
--smoke/--no-smoke flag."""
import json
import os
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core import isomap, streaming
from repro.core.pipeline import ManifoldPipeline, PipelineConfig
from repro.core.postprocess import clamp_disconnected
from repro.data import euler_isometric_swiss_roll
from repro.launch.serving import BatchedMapperService


@pytest.fixture(scope="module")
def fitted():
    """One fitted base manifold shared by the serving tests."""
    x, _ = euler_isometric_swiss_roll(320, seed=5)
    base, new = x[:256], x[256:]
    cfg = isomap.IsomapConfig(k=10, d=2, block=128)
    res = isomap.isomap(jnp.asarray(base), cfg, keep_geodesics=True)
    return base, new, res


def _mapper(fitted, **kw):
    base, _, res = fitted
    return streaming.StreamingMapper(
        jnp.asarray(base), res.geodesics, res.embedding, **kw
    )


# ------------------------------------------------- request queue service --


def test_service_results_match_direct_mapper(fitted):
    base, new, res = fitted
    mapper = _mapper(fitted, k=10, batch=16)
    want = np.asarray(mapper(jnp.asarray(new)))
    with BatchedMapperService(mapper, max_batch=16, max_latency_ms=5.0) as s:
        s.warmup(new.shape[1])
        futures = [s.submit(p) for p in new]       # one request per point
        got = np.concatenate([f.result() for f in futures])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    stats = s.stats()
    assert stats["requests"] == len(new)
    assert stats["points"] == len(new)
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"] > 0


def test_service_max_batch_flush(fitted):
    """A burst larger than max_batch must coalesce into full batches, not
    one-request flushes (generous latency so size is the only trigger)."""
    _, new, _ = fitted
    mapper = _mapper(fitted, k=10, batch=16)
    with BatchedMapperService(
        mapper, max_batch=16, max_latency_ms=10_000.0
    ) as s:
        s.warmup(new.shape[1])
        futures = [s.submit(p) for p in new]       # 64 instant arrivals
        for f in futures:
            f.result()
    stats = s.stats()
    assert stats["mean_batch"] > 1.5, stats        # actually coalescing
    assert max(s._batch_sizes) == 16               # hit the size trigger


def test_service_max_latency_flush(fitted):
    """A lone request must be served once its deadline passes even though
    the batch never fills."""
    _, new, _ = fitted
    mapper = _mapper(fitted, k=10, batch=64)
    with BatchedMapperService(
        mapper, max_batch=64, max_latency_ms=30.0
    ) as s:
        s.warmup(new.shape[1])
        t0 = time.monotonic()
        y = s.submit(new[0]).result(timeout=30)
        elapsed = time.monotonic() - t0
    assert y.shape == (1, 2)
    assert elapsed < 10, elapsed                   # did not wait for a batch
    assert s.stats()["batches"] == 1


def test_service_stop_drains_pending(fitted):
    _, new, _ = fitted
    mapper = _mapper(fitted, k=10, batch=16)
    s = BatchedMapperService(mapper, max_batch=16, max_latency_ms=50.0)
    s.start()
    s.warmup(new.shape[1])
    futures = [s.submit(p) for p in new[:10]]
    s.stop()                                       # must flush, not strand
    for f in futures:
        assert f.result(timeout=5).shape == (1, 2)


def test_service_batches_never_exceed_max_batch(fitted):
    """An arrival group that would overflow opens the next batch - the
    fixed compiled shape is preserved (no off-shape flushes)."""
    _, new, _ = fitted
    mapper = _mapper(fitted, k=10, batch=16)
    want = np.asarray(mapper(jnp.asarray(new)))
    with BatchedMapperService(
        mapper, max_batch=16, max_latency_ms=300.0
    ) as s:
        s.warmup(new.shape[1])
        futures = [s.submit(new[lo:lo + 12])       # 12+12 > 16: must split
                   for lo in range(0, 60, 12)]
        got = np.concatenate([f.result() for f in futures])
    np.testing.assert_allclose(got, want[:60], rtol=1e-5, atol=1e-6)
    assert max(s._batch_sizes) <= 16, s._batch_sizes


def test_service_group_requests_preserve_order(fitted):
    """Arrival groups of mixed sizes come back sliced per request."""
    _, new, _ = fitted
    mapper = _mapper(fitted, k=10, batch=32)
    want = np.asarray(mapper(jnp.asarray(new)))
    with BatchedMapperService(mapper, max_batch=32, max_latency_ms=5.0) as s:
        s.warmup(new.shape[1])
        f1 = s.submit(new[:3])
        f2 = s.submit(new[3:4])
        f3 = s.submit(new[4:])
        got = np.concatenate([f1.result(), f2.result(), f3.result()])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ------------------------------------------- StreamingMapper edge cases ---


def test_mapper_empty_arrival_batch(fitted):
    mapper = _mapper(fitted, k=10)
    y = np.asarray(mapper(jnp.zeros((0, 3))))
    assert y.shape == (0, 2)
    assert mapper.map_stream([]).shape == (0, 2)


def test_mapper_arrivals_not_multiple_of_batch(fitted):
    _, new, _ = fitted
    mapper = _mapper(fitted, k=10, batch=24)       # 64 arrivals -> 24/24/16
    y_chunked = np.asarray(mapper(jnp.asarray(new)))
    y_once = np.asarray(_mapper(fitted, k=10, batch=256)(jnp.asarray(new)))
    np.testing.assert_allclose(y_chunked, y_once, rtol=1e-5, atol=1e-6)


def test_mapper_k_larger_than_base(fitted):
    """k > n_base must clamp to n_base instead of crashing top_k."""
    base, new, res = fitted
    nb = 16
    mapper = streaming.StreamingMapper(
        jnp.asarray(base[:nb]), res.geodesics[:nb, :nb],
        res.embedding[:nb], k=64,
    )
    assert mapper.k == nb
    y = np.asarray(mapper(jnp.asarray(new)))
    assert y.shape == (len(new), 2)
    assert np.isfinite(y).all()


# ----------------------------------------------------- regression fixes ---


def test_map_new_points_zero_eigenvalue_column(fitted):
    """embedding_from_eig clamps negative eigenvalues to exactly 0; a zero
    column in y_base must not divide to NaN coordinates."""
    base, new, res = fitted
    y0 = np.asarray(res.embedding).copy()
    y0[:, 1] = 0.0
    y = np.asarray(streaming.map_new_points(
        jnp.asarray(new), jnp.asarray(base), res.geodesics,
        jnp.asarray(y0), k=10,
    ))
    assert np.isfinite(y).all()
    np.testing.assert_array_equal(y[:, 1], 0.0)    # degenerate dim stays 0


def test_clamp_disconnected_no_finite_offdiagonal():
    """Diameter-0 graphs (every point isolated) must clamp +inf to a
    positive sentinel, not silently collapse all distances to 0."""
    a = jnp.asarray(
        [[0.0, np.inf, np.inf],
         [np.inf, 0.0, np.inf],
         [np.inf, np.inf, 0.0]], jnp.float32,
    )
    out = np.asarray(clamp_disconnected(a))
    assert np.isfinite(out).all()
    off = out[~np.eye(3, dtype=bool)]
    assert (off > 0).all(), out                    # not collapsed
    np.testing.assert_array_equal(np.diag(out), 0.0)


def test_from_checkpoint_skips_partial_and_legacy_steps(tmp_path):
    """A concurrently GC'd step (manifest gone) and a partially written
    manifest (no "keys") must be skipped, falling back to the next-older
    complete boundary - same tolerant scan as the pipeline's resume."""
    x, _ = euler_isometric_swiss_roll(320, seed=3)
    base, new = x[:256], x[256:]
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    want = np.asarray(
        streaming.StreamingMapper.from_artifacts(art, k=10)(jnp.asarray(new))
    )

    # newest step: directory exists but manifest was GC'd mid-scan
    gone = tmp_path / "step_0000000090"
    gone.mkdir()
    # next: manifest present but partially written (no "keys" field)
    partial = tmp_path / "step_0000000091"
    partial.mkdir()
    with open(partial / "manifest.json", "w") as f:
        json.dump({"step": 91}, f)

    mgr2 = CheckpointManager(str(tmp_path), keep=10)
    mapper = streaming.StreamingMapper.from_checkpoint(mgr2, k=10)
    got = np.asarray(mapper(jnp.asarray(new)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_from_checkpoint_skips_step_gcd_after_manifest_read(tmp_path):
    """A step whose arrays vanish between the manifest read and the load
    (async-writer retention GC) must fall back, not crash."""
    x, _ = euler_isometric_swiss_roll(320, seed=3)
    base, new = x[:256], x[256:]
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(
        cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr
    ).run(jnp.asarray(base))
    want = np.asarray(
        streaming.StreamingMapper.from_artifacts(art, k=10)(jnp.asarray(new))
    )
    # newest step: complete-looking manifest, but arrays.npz is gone
    ghost = tmp_path / "step_0000000090"
    ghost.mkdir()
    with open(ghost / "manifest.json", "w") as f:
        json.dump({"step": 90, "keys": ["x", "geodesics", "embedding"]}, f)

    mapper = streaming.StreamingMapper.from_checkpoint(
        CheckpointManager(str(tmp_path), keep=10), k=10
    )
    np.testing.assert_allclose(
        np.asarray(mapper(jnp.asarray(new))), want, rtol=1e-5, atol=1e-6
    )


def test_pipeline_resume_survives_step_gcd_after_manifest_read(tmp_path):
    """Same race on the pipeline's own resume scan."""
    x, _ = euler_isometric_swiss_roll(256, seed=3)
    cfg = PipelineConfig(k=10, d=2, block=128)
    mgr = CheckpointManager(str(tmp_path), keep=10)
    art = ManifoldPipeline(cfg=cfg, checkpoint=mgr).run(jnp.asarray(x))
    ghost = tmp_path / "step_0000000090"
    ghost.mkdir()
    with open(ghost / "manifest.json", "w") as f:
        json.dump({
            "step": 90, "pipeline": "isomap", "stage": "eigen",
            "keys": sorted(art.keys()),
        }, f)
    art2 = ManifoldPipeline(
        cfg=cfg, checkpoint=CheckpointManager(str(tmp_path), keep=10)
    ).run(jnp.asarray(x), resume=True)
    np.testing.assert_array_equal(
        np.asarray(art["embedding"]), np.asarray(art2["embedding"])
    )


def test_pipeline_resume_rejects_same_shape_different_data(tmp_path):
    """Shape alone can't tell a seed-0 fit from a seed-1 run; resuming
    with different same-shape points must error, not silently serve the
    stale embedding."""
    x0, _ = euler_isometric_swiss_roll(256, seed=0)
    x1, _ = euler_isometric_swiss_roll(256, seed=1)
    cfg = PipelineConfig(k=10, d=2, block=128)
    mgr = CheckpointManager(str(tmp_path), keep=10)
    ManifoldPipeline(cfg=cfg, checkpoint=mgr).run(jnp.asarray(x0))
    with pytest.raises(ValueError, match="does not match"):
        ManifoldPipeline(
            cfg=cfg, checkpoint=CheckpointManager(str(tmp_path), keep=10)
        ).run(jnp.asarray(x1), resume=True)


def test_from_checkpoint_still_raises_when_nothing_usable(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    (tmp_path / "step_0000000007").mkdir()         # manifest-less junk only
    with pytest.raises(FileNotFoundError):
        streaming.StreamingMapper.from_checkpoint(mgr)


# ------------------------------------------------------------- serve CLI --


def test_serve_cli_smoke_flag_is_toggleable():
    """--smoke was store_true with default=True: full configs unreachable.
    BooleanOptionalAction restores --no-smoke."""
    from repro.launch.serve import build_parser

    ap = build_parser()
    assert ap.parse_args(["--arch", "smollm-135m"]).smoke is True
    assert ap.parse_args(["--arch", "smollm-135m", "--smoke"]).smoke is True
    assert ap.parse_args(["--arch", "smollm-135m", "--no-smoke"]).smoke \
        is False


def test_serve_manifold_reports_queue_stats(tmp_path):
    from repro.launch.serve import serve_manifold

    out = serve_manifold(
        n_base=256, n_stream=32, stream_batch=16, max_latency_ms=10.0,
        block=128, checkpoint_dir=str(tmp_path),
    )
    assert out["requests"] == 32
    assert np.isfinite(out["latency_p50_ms"])
    assert out["latency_p99_ms"] >= out["latency_p50_ms"]
    assert out["points_per_s"] > 0


# ------------------------------------------------ rolling stats window ----


def test_stats_memory_stays_flat_over_sustained_traffic():
    """10k requests must not grow the latency/occupancy buffers past the
    rolling window (they used to be unbounded lists), while the lifetime
    counters keep the true totals."""
    window = 128
    with BatchedMapperService(
        lambda x: np.zeros((x.shape[0], 2), np.float32),
        max_batch=8, max_latency_ms=0.1, stats_window=window,
    ) as s:
        futures = [s.submit(np.zeros(3, np.float32)) for _ in range(10_000)]
        for f in futures:
            f.result(timeout=60)
    assert len(s._latencies) <= window
    assert len(s._batch_sizes) <= window
    stats = s.stats()
    assert stats["requests"] == 10_000           # lifetime, not windowed
    assert stats["points"] == 10_000
    assert stats["window"] <= window
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"] > 0


def test_stats_window_validation():
    with pytest.raises(ValueError, match="stats_window"):
        BatchedMapperService(lambda x: x, stats_window=0)


# ------------------------------------------------- absorb coordination ----


class _AbsorbableMapper:
    """Callable mapper with a recorded absorb() - tracks interleaving."""

    def __init__(self):
        self.calls = []

    def __call__(self, x):
        self.calls.append(("map", x.shape[0]))
        return np.zeros((x.shape[0], 2), np.float32)

    def absorb(self, x):
        self.calls.append(("absorb", x.shape[0]))
        import types

        return types.SimpleNamespace(absorbed=x.shape[0])


def test_absorb_runs_between_flushes():
    """An admitted absorb executes on the scheduler thread, serialized
    with read flushes, and resolves its future with the report."""
    mapper = _AbsorbableMapper()
    with BatchedMapperService(
        mapper, max_batch=4, max_latency_ms=2.0
    ) as s:
        r1 = s.submit(np.zeros((2, 3), np.float32))
        fut = s.submit_absorb(np.zeros((6, 3), np.float32))
        r2 = s.submit(np.zeros((2, 3), np.float32))
        assert fut.result(timeout=30).absorbed == 6
        r1.result(timeout=30), r2.result(timeout=30)
    kinds = [k for k, _ in mapper.calls]
    assert "absorb" in kinds
    assert s.stats()["absorbed"] == 6
    assert s.stats()["absorb_calls"] == 1


def test_absorb_rejected_when_queue_hot():
    """Admission control: with more requests waiting than the admission
    limit, submit_absorb fails fast instead of head-of-line blocking."""
    import threading

    from repro.launch.serving import AbsorbRejected

    gate = threading.Event()

    def slow_mapper(x):
        gate.wait(30)
        return np.zeros((x.shape[0], 2), np.float32)

    slow_mapper.absorb = lambda x: None
    s = BatchedMapperService(
        slow_mapper, max_batch=1, max_latency_ms=1.0, absorb_admission=2
    )
    with s:
        futures = [s.submit(np.zeros(3, np.float32)) for _ in range(8)]
        # the scheduler is stuck in the first flush; > 2 requests queued
        fut = s.submit_absorb(np.zeros((4, 3), np.float32))
        with pytest.raises(AbsorbRejected, match="read queue hot"):
            fut.result(timeout=5)
        gate.set()
        for f in futures:
            f.result(timeout=30)


def test_absorb_errors_surface_via_future():
    def mapper(x):
        return np.zeros((x.shape[0], 2), np.float32)

    # a mapper without absorb(): the future carries the AttributeError
    with BatchedMapperService(mapper, max_batch=4) as s:
        fut = s.submit_absorb(np.zeros((2, 3), np.float32))
        with pytest.raises(AttributeError):
            fut.result(timeout=30)


@pytest.mark.parametrize("depth", [1, 2])
def test_flush_errors_surface_via_future(depth):
    """A failed flush fails each request in it through its own future, on
    the scheduler thread and on the worker pool alike, and the service
    keeps serving: a mapper error, and a batch that cannot even be
    assembled (feature widths differ), both reach f.result()."""
    def mapper(x):
        if x.shape[1] == 5:
            raise RuntimeError("mapper failed")
        return np.zeros((x.shape[0], 2), np.float32)

    with BatchedMapperService(
        mapper, max_batch=4, max_latency_ms=20.0, pipeline_depth=depth
    ) as s:
        with pytest.raises(RuntimeError, match="mapper failed"):
            s.submit(np.zeros((2, 5), np.float32)).result(timeout=30)
        mixed = [s.submit(np.zeros((1, w), np.float32)) for w in (3, 4)]
        for f in mixed:
            with pytest.raises(ValueError):
                f.result(timeout=30)
        assert s.map(np.zeros((2, 3), np.float32)).shape == (2, 2)


# --------------------------------------------------- pipelined dispatch --


class _SlowIdentityMapper:
    """Thread-safe mapper with a fixed per-flush latency: sleeps (GIL
    released), echoes the input's first 2 columns so per-request results
    stay checkable through batching + pipelining."""

    def __init__(self, delay_s=0.03):
        self.delay_s = delay_s

    def __call__(self, x):
        time.sleep(self.delay_s)
        return np.asarray(x, np.float32)[:, :2]


def test_pipelined_dispatch_overlaps_flushes():
    """pipeline_depth>1 keeps several flushes in flight: wall time beats
    the serial sum, inflight_peak shows real overlap, and every request
    still gets its own rows back."""
    delay = 0.04
    mapper = _SlowIdentityMapper(delay)
    n_flushes = 6
    xs = [
        np.full((4, 3), float(i), np.float32) for i in range(n_flushes)
    ]
    with BatchedMapperService(
        mapper, max_batch=4, max_latency_ms=1.0, pipeline_depth=3
    ) as s:
        t0 = time.perf_counter()
        futures = [s.submit(x) for x in xs]
        got = [f.result(timeout=30) for f in futures]
        wall = time.perf_counter() - t0
    for i, y in enumerate(got):
        np.testing.assert_array_equal(y, xs[i][:, :2])
    stats = s.stats()
    assert stats["pipeline_depth"] == 3
    assert stats["inflight_peak"] >= 2, stats
    assert wall < n_flushes * delay * 0.9, (wall, n_flushes * delay)


def test_pipeline_depth_one_is_strictly_serial():
    mapper = _SlowIdentityMapper(0.0)
    with BatchedMapperService(mapper, max_batch=4) as s:
        futures = [
            s.submit(np.full((2, 3), float(i), np.float32))
            for i in range(5)
        ]
        for f in futures:
            f.result(timeout=30)
    stats = s.stats()
    assert stats["pipeline_depth"] == 1
    assert stats["inflight_peak"] <= 1


def test_pipeline_depth_validation():
    with pytest.raises(ValueError, match="pipeline_depth"):
        BatchedMapperService(_SlowIdentityMapper(), pipeline_depth=0)


class _OverlapProbe:
    """Counts concurrently active flushes and records any absorb that
    runs while a flush is still in flight."""

    def __init__(self):
        import threading

        self.lock = threading.Lock()
        self.active = 0
        self.peak = 0
        self.absorb_overlaps = []

    def __call__(self, x):
        with self.lock:
            self.active += 1
            self.peak = max(self.peak, self.active)
        time.sleep(0.02)
        with self.lock:
            self.active -= 1
        return np.zeros((x.shape[0], 2), np.float32)

    def absorb(self, x):
        import types

        with self.lock:
            if self.active:
                self.absorb_overlaps.append(self.active)
        return types.SimpleNamespace(absorbed=x.shape[0])


def test_pipelined_absorb_never_overlaps_flushes():
    """The single-writer guarantee survives pipelining: the scheduler
    drains every in-flight flush before an absorb touches the mapper,
    even at depth 3 with reads queued on both sides."""
    probe = _OverlapProbe()
    with BatchedMapperService(
        probe, max_batch=2, max_latency_ms=1.0, pipeline_depth=3,
        absorb_admission=100,
    ) as s:
        futures = [
            s.submit(np.zeros((2, 3), np.float32)) for _ in range(6)
        ]
        absorb_fut = s.submit_absorb(np.zeros((4, 3), np.float32))
        futures += [
            s.submit(np.zeros((2, 3), np.float32)) for _ in range(6)
        ]
        assert absorb_fut.result(timeout=30).absorbed == 4
        for f in futures:
            f.result(timeout=30)
    assert probe.peak >= 2          # pipelining actually happened
    assert not probe.absorb_overlaps, probe.absorb_overlaps
    assert s.stats()["absorbed"] == 4
