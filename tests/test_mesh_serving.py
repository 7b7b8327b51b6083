"""Mesh tests for the approximate/streaming tail: the sharded landmark
Bellman-Ford rows and the sharded new-point anchor relaxation must agree
with the LocalBackend results within 1e-5 on a >=4-device mesh, and the
batched request queue must serve correctly on top of the mesh mapper.

Runs in a subprocess with 8 fake CPU devices so the rest of the suite
keeps the real 1-device view."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import isomap, streaming
from repro.core.pipeline import MeshBackend
from repro.data import euler_isometric_swiss_roll
from repro.launch.mesh import make_mesh
from repro.launch.serving import BatchedMapperService

mesh = make_mesh((4, 2), ("data", "model"))
n = 256
x, latent = euler_isometric_swiss_roll(n + 64, seed=1)
x = np.pad(x, ((0, 0), (0, 1)))  # 4 features so the model axis divides
xb, xs = jnp.asarray(x[:n]), jnp.asarray(x[n:])

# landmark tail: local vs mesh backend through the same LandmarkStage
y_l, le_l = isomap.landmark_isomap(xb, k=10, m=32, d=2)
y_s, le_s = isomap.landmark_isomap(xb, k=10, m=32, d=2, mesh=mesh)
np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_l),
                           rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(le_s), np.asarray(le_l),
                           rtol=1e-5, atol=1e-5)
print("OK sharded-landmark")

# streaming relaxation: local vs sharded on identical fitted artifacts
cfg = isomap.IsomapConfig(k=10, d=2, block=64)
res = isomap.isomap(xb, cfg, keep_geodesics=True)
y_new_l = np.asarray(streaming.map_new_points(
    xs, xb, res.geodesics, res.embedding, k=10))
y_new_s = np.asarray(streaming.map_new_points_sharded(
    xs, xb, res.geodesics, res.embedding, mesh, k=10))
np.testing.assert_allclose(y_new_s, y_new_l, rtol=1e-5, atol=1e-5)
print("OK sharded-map-new-points")

# StreamingMapper dispatching through MeshBackend (state device_put once)
backend = MeshBackend(mesh)
mapper = streaming.StreamingMapper(
    xb, res.geodesics, res.embedding, k=10, batch=32, backend=backend)
y_mb = np.asarray(mapper(xs))
np.testing.assert_allclose(y_mb, y_new_l, rtol=1e-5, atol=1e-5)
print("OK mesh-mapper")

# the request queue on top of the mesh mapper
with BatchedMapperService(mapper, max_batch=32, max_latency_ms=25.0) as s:
    s.warmup(xs.shape[1])
    futures = [s.submit(np.asarray(xs[i])) for i in range(len(xs))]
    y_q = np.concatenate([f.result() for f in futures])
np.testing.assert_allclose(y_q, y_new_l, rtol=1e-5, atol=1e-5)
stats = s.stats()
assert stats["requests"] == len(xs), stats
assert stats["mean_batch"] > 1.0, stats  # scheduler actually coalesced
print("OK mesh-queue", round(stats["mean_batch"], 1))

# end-to-end: mesh pipeline artifacts -> mesh mapper, vs local oracle
xbs = jax.device_put(xb, NamedSharding(mesh, P("data", "model")))
res_d = isomap.isomap_distributed(xbs, cfg, mesh)
mapper_d = streaming.StreamingMapper(
    xbs, res_d.geodesics, res_d.embedding, k=10, backend=backend)
y_d = np.asarray(mapper_d(xs))
y_o = np.asarray(streaming.map_new_points(
    xs, xb, res_d.geodesics, res_d.embedding, k=10))
np.testing.assert_allclose(y_d, y_o, rtol=1e-5, atol=1e-5)
print("OK mesh-e2e-serving")

# absorb on mesh vs local: same arrivals folded into the same base fit
# must grow the same geodesic system within 1e-5 (the augmented-graph
# edges are built on the gathered base, so the structure is identical;
# only min-plus schedules differ).  The mesh flush multiple is
# lcm(4, 2) = 4; 16 arrivals flush completely on both backends.
mapper_loc = streaming.StreamingMapper(
    xb, res.geodesics, res.embedding, k=10, batch=32)
mapper_mesh = streaming.StreamingMapper(
    xb, res.geodesics, res.embedding, k=10, batch=32, backend=backend)
assert mapper_mesh.backend.absorb_multiple == 4
rep_l = mapper_loc.absorb(np.asarray(xs[:16]))
rep_m = mapper_mesh.absorb(np.asarray(xs[:16]))
assert rep_l.absorbed == rep_m.absorbed == 16, (rep_l, rep_m)
assert mapper_mesh.version == 1 and mapper_mesh.n_base == n + 16
np.testing.assert_allclose(
    np.asarray(mapper_mesh.geodesics), np.asarray(mapper_loc.geodesics),
    rtol=1e-5, atol=1e-5)
y_l2 = np.asarray(mapper_loc(xs[16:]))
y_m2 = np.asarray(mapper_mesh(xs[16:]))
sign = np.sign(np.sum(y_l2 * y_m2, axis=0))  # eigen sign is arbitrary
np.testing.assert_allclose(y_m2 * sign, y_l2, rtol=1e-4, atol=1e-4)
print("OK mesh-absorb")
print("ALL-MESH-SERVING-OK")
"""


@pytest.mark.slow
def test_mesh_serving_suite():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "ALL-MESH-SERVING-OK" in proc.stdout
