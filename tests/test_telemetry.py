"""The program's own observability: named scopes inside the traced bodies
of every stage, host spans on the fit and flush paths that never wait for
the device, and the service's time counters."""
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    apsp, centering, graph, knn, pipeline, postprocess, sparse, spectral,
    streaming, telemetry,
)
from repro.data import euler_isometric_swiss_roll
from repro.launch.serving import TIME_COUNTERS, BatchedMapperService

#: entries JAX itself puts on the name stack
_STACK = {"while", "body", "cond", "closed_call", "shard_map", "pjit"}


def _scopes(lowered) -> set:
    """Scope paths of every op of a compiled program: its HLO metadata's
    ``op_name`` (what a device trace attributes each op by) with JAX's
    own name-stack entries and the primitive left out."""
    text = lowered.compile().as_text()
    out = set()
    # names not rooted at the program (reducers' and comparators' own
    # computations) are no ops a trace shows
    for name in re.findall(r'op_name="(jit\([^"]+)"', text):
        parts = [p for p in name.split("/")[:-1]
                 if p and "(" not in p and p not in _STACK
                 and not p.startswith("branch_")]
        out.add("/".join(parts))
    return out


@pytest.fixture(scope="module")
def small():
    x, _ = euler_isometric_swiss_roll(256, seed=3)
    x = jnp.asarray(x, jnp.float32)
    d, i = knn.knn_blocked(x, k=6, block=128)
    return x, d, i


def _lowered(stage, small):
    x, d, i = small
    n = x.shape[0]
    f32 = jnp.float32
    if stage == "knn":
        return knn.knn_blocked.lower(x, k=6, block=128)
    if stage == "graph":
        return graph.knn_to_graph.lower(d, i, n=n)
    if stage == "csr_graph":
        return graph._padded_csr_device.lower(d, i, n=n, deg=12)
    if stage == "apsp":
        return apsp.apsp_blocked_segment.lower(
            jnp.ones((n, n), f32), jnp.int32(0), jnp.int32(2), block=128)
    if stage == "clamp":
        return jax.jit(postprocess.clamp_disconnected).lower(
            jnp.ones((n, n), f32))
    if stage == "center":
        return centering.double_center.lower(jnp.ones((n, n), f32))
    if stage == "eigen":
        return spectral.power_iteration.lower(jnp.eye(n, dtype=f32), d=2)
    if stage == "sparse_geodesics":
        nbr, w = graph.knn_to_padded_csr(d, i, n=n)
        return sparse.sparse_panel_segment.lower(
            nbr, w, jnp.arange(8, dtype=jnp.int32),
            jnp.full((8, n), jnp.inf, f32), jnp.int32(0), jnp.int32(1),
            jnp.float32(1.0), bs=8, bucket=2, bn=128, mode="pallas")
    if stage == "sparse_embed":
        return sparse.landmark_mds_general.lower(
            jnp.ones((8, n), f32), jnp.arange(8, dtype=jnp.int32), d=2)
    if stage == "map":
        return streaming.map_new_points.lower(
            x[:4], x, jnp.ones((n, n), f32), jnp.ones((n, 2), f32), k=6)
    raise ValueError(stage)


@pytest.mark.parametrize("stage, want", [
    ("knn", {"knn"}),
    ("graph", {"graph"}),
    ("csr_graph", {"csr_graph"}),
    ("apsp", {"apsp/diag", "apsp/panels", "apsp/update"}),
    ("clamp", {"clamp"}),
    ("center", {"center"}),
    ("eigen", {"eigen"}),
    ("sparse_geodesics", {"sparse_geodesics/gather",
                          "sparse_geodesics/frontier_relax"}),
    ("sparse_embed", {"sparse_embed", "sparse_embed/eigen"}),
    ("map", {"map"}),
])
def test_each_stage_program_carries_its_scope(stage, want, small):
    got = _scopes(_lowered(stage, small))
    assert want <= got, (stage, sorted(got))
    # every op of the program sits under the stage's scope
    root = stage
    assert all(s == root or s.startswith(root + "/") for s in got if s), (
        sorted(got))


def test_mesh_apsp_exchange_carries_its_scope():
    """The panel broadcasts of the sharded APSP sit under apsp/exchange
    (a one-device mesh lowers the same body)."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    seg = apsp.make_apsp_segment(mesh, n=256, b=128, split_panels=False)
    got = _scopes(seg.lower(jnp.ones((256, 256), jnp.float32),
                            jnp.int32(0), jnp.int32(1)))
    assert {"apsp/exchange", "apsp/diag", "apsp/panels",
            "apsp/update"} <= got, sorted(got)


@pytest.fixture
def opened(monkeypatch):
    """Records every program span opened: [(name, thread name)]."""
    seen = []
    real = telemetry.span

    def span(name):
        seen.append((name, threading.current_thread().name))
        return real(name)

    monkeypatch.setattr(telemetry, "span", span)
    return seen


def test_pipeline_run_spans_stages_and_never_blocks(monkeypatch, opened):
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    x, _ = euler_isometric_swiss_roll(256, seed=4)
    cfg = pipeline.PipelineConfig(k=8, d=2, block=128, regime="dense")
    pipe = pipeline.ManifoldPipeline(
        pipeline.stages_for(cfg, 256), cfg=cfg)
    art = pipe.run(jnp.asarray(x, jnp.float32))
    assert calls == []
    names = [n for n, _ in opened]
    assert names == ["fit"] + ["stage:" + s.name for s in pipe.stages]
    assert np.isfinite(np.asarray(art["embedding"])).all()


def test_landmark_selection_is_spanned(opened):
    """The host selection opens its two phase spans, in order."""
    from repro.core.landmarks import hierarchical_landmarks

    x, _ = euler_isometric_swiss_roll(512, seed=5)
    d, _ = knn.knn_blocked(jnp.asarray(x, jnp.float32), k=6, block=128)
    lm = hierarchical_landmarks(x, np.asarray(d), m=40)
    assert lm.shape == (40,)
    assert [n for n, _ in opened] == ["landmarks:cover", "landmarks:fill"]


def test_pipeline_checkpoint_saves_are_spanned(tmp_path, opened):
    from repro.checkpoint import CheckpointManager

    x, _ = euler_isometric_swiss_roll(256, seed=4)
    cfg = pipeline.PipelineConfig(k=8, d=2, block=128, regime="dense")
    pipe = pipeline.ManifoldPipeline(
        pipeline.stages_for(cfg, 256), cfg=cfg,
        checkpoint=CheckpointManager(str(tmp_path)))
    pipe.run(jnp.asarray(x, jnp.float32))
    names = [n for n, _ in opened]
    # one save per stage boundary, and the final wait
    assert names.count("checkpoint") == len(pipe.stages) + 1


class _SleepyMapper:
    """Maps (m, D) -> (m, 2) after sleeping ``delay`` seconds."""

    def __init__(self, delay: float):
        self.delay = delay

    def __call__(self, x):
        time.sleep(self.delay)
        return np.asarray(x)[:, :2] * 2.0


@pytest.mark.parametrize("depth", [1, 2])
def test_service_time_counters_split_latency(depth, opened):
    """A burst of requests through a mapper that takes 5 ms: the queue
    waits and service times add up to what the clients saw, to 1% (the
    burst makes the latencies long next to a reply's own cost)."""
    delay = 0.005
    done = {}
    lock = threading.Lock()
    rng = np.random.default_rng(0)
    reqs = [rng.normal(size=(int(rng.integers(1, 9)), 3)) for _ in range(60)]
    with BatchedMapperService(_SleepyMapper(delay), max_batch=16,
                              max_latency_ms=2.0,
                              pipeline_depth=depth) as svc:
        t_sub = []
        for i, x in enumerate(reqs):
            t_sub.append(time.monotonic())

            def cb(fut, i=i):
                with lock:
                    done[i] = time.monotonic()

            svc.submit(x).add_done_callback(cb)
        deadline = time.monotonic() + 30
        while len(done) < len(reqs) and time.monotonic() < deadline:
            time.sleep(0.005)
        s = svc.stats()
    assert len(done) == len(reqs)
    assert set(TIME_COUNTERS) <= set(s)
    assert s["map_call_s"] >= s["batches"] * delay
    latency = sum(done[i] - t_sub[i] for i in range(len(reqs)))
    assert s["queue_wait_s"] + s["service_s"] == pytest.approx(
        latency, rel=0.01)
    assert s["service_s"] >= s["requests"] * delay
    assert min(s[k] for k in TIME_COUNTERS) >= 0.0
    names = {n for n, _ in opened}
    assert {"serve:coalesce", "serve:pack", "serve:map", "serve:fetch",
            "serve:reply"} <= names
    # the flush phases run where the flush runs: on the worker pool
    # when the service pipelines its flushes
    threads = {t for n, t in opened if n == "serve:map"}
    assert all(t.startswith("mapper-flush") for t in threads) == (depth > 1)


def test_service_counters_start_at_zero():
    with BatchedMapperService(_SleepyMapper(0.0), max_batch=4) as svc:
        s = svc.stats()
    assert {k: s[k] for k in TIME_COUNTERS} == dict.fromkeys(
        TIME_COUNTERS, 0.0)


def test_mapper_put_is_spanned(opened):
    x, _ = euler_isometric_swiss_roll(64, seed=1)
    x = jnp.asarray(x, jnp.float32)
    a = jnp.ones((64, 64), jnp.float32)
    mapper = streaming.StreamingMapper(x, a, jnp.ones((64, 2)), k=4)
    mapper(np.asarray(x[:3]))
    assert ("map:put", threading.current_thread().name) in opened


def test_span_names_carry_the_program_prefix():
    assert telemetry.PREFIX == "repro:"
    with telemetry.span("fit") as ann:
        assert isinstance(ann, jax.profiler.TraceAnnotation)
