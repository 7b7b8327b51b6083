"""Mesh-elastic resume: a pipeline checkpointed on one mesh shape must
restore onto a *different* mesh shape, with every artifact device_put
straight onto the new mesh's tile sharding (placement-aware restore), and
finish with an embedding matching the uninterrupted run.

Covers the acceptance scenario (fit on 4x2 killed at the center boundary,
resume on 2x4) plus the harder mid-APSP variant: a segment checkpoint
written halfway through the panel loop on 4x2 re-enters the panel loop on
2x4.  Runs in a subprocess with 8 fake CPU devices so the rest of the
suite keeps the real 1-device view."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.core.pipeline import (
    APSPStage, ManifoldPipeline, MeshBackend, PipelineConfig, isomap_stages,
)
from repro.data import euler_isometric_swiss_roll
from repro.launch.mesh import make_mesh

n = 256
x, _ = euler_isometric_swiss_roll(n, seed=1)
x = np.pad(x, ((0, 0), (0, 1)))  # 4 features so the model axis divides
cfg = PipelineConfig(k=10, d=2, block=64)

mesh_a = make_mesh((4, 2), ("data", "model"))
xa = jax.device_put(jnp.asarray(x), NamedSharding(mesh_a, P("data", "model")))
oracle = ManifoldPipeline(
    isomap_stages(), backend=MeshBackend(mesh_a), cfg=cfg
).run(xa)

mesh_b = make_mesh((2, 4), ("data", "model"))
xb = jax.device_put(jnp.asarray(x), NamedSharding(mesh_b, P("data", "model")))

def assert_embedding_close(got, want, tol=1e-5):
    # eigenvector columns have arbitrary sign; the eigen stage ran on a
    # different mesh shape than the oracle, so align signs per column
    got, want = np.asarray(got), np.asarray(want)
    signs = np.sign(np.sum(got * want, axis=0))
    signs[signs == 0] = 1.0
    np.testing.assert_allclose(got * signs, want, rtol=tol, atol=tol)

# ---- boundary elastic resume: fit on 4x2, kill after `center`, resume 2x4
with tempfile.TemporaryDirectory() as td:
    mgr = CheckpointManager(td, keep=50)
    ManifoldPipeline(
        isomap_stages()[:5], backend=MeshBackend(mesh_a), cfg=cfg,
        checkpoint=mgr,
    ).run(xa)
    assert mgr.read_manifest(mgr.latest_step())["stage"] == "center"

    mgr2 = CheckpointManager(td, keep=50)
    pipe_b = ManifoldPipeline(
        isomap_stages(), backend=MeshBackend(mesh_b), cfg=cfg,
        checkpoint=mgr2,
    )
    point = pipe_b._find_resume_point()
    assert point.start == 5, point.start  # re-enter at eigen
    # restored artifacts carry tile placements, landed on the NEW mesh
    for key in ("geodesics", "gram"):
        placed = pipe_b.backend.place(
            point.artifacts[key], point.placements[key]
        )
        assert placed.sharding.mesh.devices.shape == (2, 4), key
        assert tuple(placed.sharding.spec) == ("data", "model"), key
    art_b = pipe_b.run(xb, resume=True)
    assert_embedding_close(art_b["embedding"], oracle["embedding"])
    # pruning held across the restart: eigen boundary has no gram
    final = mgr2.read_manifest(mgr2.latest_step())
    assert final["stage"] == "eigen"
    assert not {"graph", "geodesics_raw", "gram"} & set(final["keys"])
print("OK elastic-boundary-4x2-to-2x4")

# ---- mid-APSP elastic resume: segment checkpoint on 4x2, continue on 2x4
class Boom(Exception):
    pass

class ExplodingAPSP(APSPStage):
    def run_segment(self, ctx, art, state, lo, hi):
        if lo >= 2:
            raise Boom()
        return super().run_segment(ctx, art, state, lo, hi)

with tempfile.TemporaryDirectory() as td:
    mgr = CheckpointManager(td, keep=50)
    stages = [
        s if s.name != "apsp" else ExplodingAPSP() for s in isomap_stages()
    ]
    pipe = ManifoldPipeline(
        stages, cfg=cfg, checkpoint=mgr,
        backend=MeshBackend(mesh_a, segment=1),
    )
    try:
        pipe.run(xa)
        raise SystemExit("expected the injected mid-APSP crash")
    except Boom:
        pass
    mgr.wait()
    partial = mgr.read_manifest(mgr.latest_step())
    assert partial["partial"] and partial["segment"] == 2, partial
    assert partial["placements"]["_segstate/g"] == ["data", "model"]

    segs = []

    class TrackingAPSP(APSPStage):
        def run_segment(self, ctx, art, state, lo, hi):
            segs.append((int(lo), int(hi)))
            assert state["g"].sharding.mesh.devices.shape == (2, 4)
            return super().run_segment(ctx, art, state, lo, hi)

    stages2 = [
        s if s.name != "apsp" else TrackingAPSP() for s in isomap_stages()
    ]
    mgr2 = CheckpointManager(td, keep=50)
    art = ManifoldPipeline(
        stages2, cfg=cfg, checkpoint=mgr2,
        backend=MeshBackend(mesh_b, segment=1),
    ).run(xb, resume=True)
    assert segs == [(2, 3), (3, 4)], segs
    assert_embedding_close(art["embedding"], oracle["embedding"])
print("OK elastic-mid-apsp-4x2-to-2x4")

# ---- serve driver: fit + checkpoint on 4x2, restart serving on 2x4
from repro.launch.serve import serve_manifold
with tempfile.TemporaryDirectory() as td:
    out = serve_manifold(
        n_base=256, n_stream=32, stream_batch=16, block=64,
        checkpoint_dir=td, mesh_shape=(4, 2), max_latency_ms=5.0,
    )
    out2 = serve_manifold(
        n_base=256, n_stream=32, stream_batch=16, block=64,
        checkpoint_dir=td, mesh_shape=(2, 4), resume=True,
        max_latency_ms=5.0,
    )
    assert out2["fit_s"] < out["fit_s"], (out["fit_s"], out2["fit_s"])
    assert abs(out2["procrustes_error"] - out["procrustes_error"]) < 1e-4, (
        out["procrustes_error"], out2["procrustes_error"],
    )
print("OK elastic-serve-restart")
print("ALL-ELASTIC-OK")
"""


@pytest.mark.slow
def test_mesh_elastic_resume_suite():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "ALL-ELASTIC-OK" in proc.stdout
