"""Multi-device tests: one subprocess with 8 fake CPU devices runs every
check (the rest of the suite keeps the real 1-device view), and each check
it reports is one test case."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import isomap, metrics, knn, graph, apsp, centering, spectral
from repro.data import euler_isometric_swiss_roll
from repro.launch.mesh import make_mesh
from repro.sharding.logical import folded_axis_index, mesh_axis_size

mesh = make_mesh((4, 2), ("data", "model"))

assert mesh_axis_size(mesh, "data") == 4
assert mesh_axis_size(mesh, "model") == 2
assert mesh_axis_size(mesh, ("data", "model")) == 8
assert mesh_axis_size(mesh, ["model", "data"]) == 8
print("OK mesh-axis-size")

# each shard writes its folded index; row-major over the named order
def shard_index(axis):
    def body(_):
        return folded_axis_index(axis).reshape(1)
    fn = jax.shard_map(body, mesh=mesh, in_specs=P(("data", "model")),
                       out_specs=P(("data", "model")))
    return np.asarray(fn(jnp.zeros(8)))
pos = np.arange(8)  # shard position: data = pos // 2, model = pos % 2
np.testing.assert_array_equal(shard_index(("data", "model")), pos)
np.testing.assert_array_equal(shard_index(("model", "data")),
                              (pos % 2) * 4 + pos // 2)
np.testing.assert_array_equal(shard_index("data"), pos // 2)
np.testing.assert_array_equal(shard_index("model"), pos % 2)
print("OK folded-axis-index")

n = 512
x, latent = euler_isometric_swiss_roll(n, seed=1)
x = jnp.asarray(np.pad(x, ((0, 0), (0, 1))))
xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))

d_l, i_l = knn.knn_blocked(x, k=10, block=128)
d_r, i_r = knn.knn_ring(xs, k=10, mesh=mesh)
np.testing.assert_allclose(np.sort(d_r, 1), np.sort(d_l, 1), rtol=1e-3, atol=1e-4)
print("OK ring-knn")

# row counts that do not divide the mesh: pad + strip, bit-exact vs the
# blocked single-device path (500 % 4 != 0)
d_nl, i_nl = knn.knn_blocked(x[:500], k=10, block=500)
d_nr, i_nr = knn.knn_ring(x[:500], k=10, mesh=mesh, feat_axis=None)
np.testing.assert_array_equal(np.asarray(d_nr), np.asarray(d_nl))
np.testing.assert_array_equal(np.asarray(i_nr), np.asarray(i_nl))
print("OK ring-knn-nondividing")

g = graph.knn_to_graph(d_l, i_l, n=n)
a_local = apsp.apsp_blocked(g, block=128)
gs = jax.device_put(np.asarray(g), NamedSharding(mesh, P("data", "model")))
a_shard = apsp.apsp_sharded(gs, mesh, b=64)
np.testing.assert_allclose(np.asarray(a_shard), np.asarray(a_local), rtol=1e-4, atol=1e-4)
print("OK sharded-apsp")

calls = []
a_seg = apsp.apsp_sharded(gs, mesh, b=64, segment=4,
                          checkpoint_cb=lambda g_, it: calls.append(it))
np.testing.assert_allclose(np.asarray(a_seg), np.asarray(a_local), rtol=1e-4, atol=1e-4)
assert calls == [4, 8], calls
print("OK segmented-apsp")

b_local = centering.double_center(jnp.square(a_local))
b_shard = centering.double_center_sharded(jnp.square(a_shard), mesh)
np.testing.assert_allclose(np.asarray(b_shard), np.asarray(b_local), rtol=1e-3, atol=1e-2)
print("OK sharded-centering")

eig_fn = spectral.make_power_iteration_sharded(mesh, n=n, d=2, max_iter=100, tol=1e-9)
eig_s = eig_fn(jax.device_put(np.asarray(b_local), NamedSharding(mesh, P("data", "model"))))
eig_l = spectral.power_iteration(b_local, d=2, max_iter=100, tol=1e-9)
np.testing.assert_allclose(np.asarray(eig_s.eigenvalues), np.asarray(eig_l.eigenvalues), rtol=1e-3)
print("OK sharded-power-iteration")

res = isomap.isomap_distributed(xs, isomap.IsomapConfig(k=10, d=2, block=64), mesh)
err = float(metrics.procrustes_error(res.embedding, jnp.asarray(latent)))
assert err < 5e-2, err
print("OK distributed-e2e", err)

"""


CHECKS = (
    "mesh-axis-size", "folded-axis-index", "ring-knn",
    "ring-knn-nondividing", "sharded-apsp", "segmented-apsp",
    "sharded-centering", "sharded-power-iteration", "distributed-e2e",
)


@pytest.fixture(scope="module")
def distributed_run():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )


@pytest.mark.slow
@pytest.mark.parametrize("check", CHECKS)
def test_distributed_suite(distributed_run, check):
    proc = distributed_run
    passed = {
        line.split()[1] for line in proc.stdout.splitlines()
        if line.startswith("OK ")
    }
    assert check in passed, proc.stdout + "\n" + proc.stderr
