"""End-to-end *distributed* Isomap on a simulated multi-device mesh -
the laptop-scale twin of the production 16x16 pod run (paper SIV).

Demonstrates every distributed component: ring kNN, communication-avoiding
blocked Floyd-Warshall APSP with segment checkpointing, sharded double
centering, and the distributed simultaneous power iteration.

    python examples/swissroll_end_to_end.py          # 8 simulated devices
"""
import os
import tempfile

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.core import isomap, metrics  # noqa: E402
from repro.data import euler_isometric_swiss_roll  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    n = 512
    x, latent = euler_isometric_swiss_roll(n, seed=1)
    x = np.pad(x, ((0, 0), (0, 1)))  # D=4 so features shard 2-way

    mesh = make_mesh((4, 2), ("data", "model"))
    xs = jax.device_put(
        jnp.asarray(x), NamedSharding(mesh, P("data", "model"))
    )

    # fault tolerance: APSP checkpoints every 4 diagonal panels (the
    # paper's every-10-iterations RDD checkpoint, as a restart unit)
    mgr = CheckpointManager(tempfile.mkdtemp(prefix="isomap_ckpt-"))
    saved = []

    def ckpt_cb(g, next_iter):
        mgr.save(next_iter, {"apsp": g})
        saved.append(next_iter)

    cfg = isomap.IsomapConfig(k=10, d=2, block=64)
    res = isomap.isomap_distributed(
        xs, cfg, mesh, checkpoint_cb=ckpt_cb, segment=4
    )
    mgr.wait()

    err = metrics.procrustes_error(res.embedding, jnp.asarray(latent))
    print(f"mesh            : {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    print(f"APSP checkpoints: panels {saved}")
    print(f"power iters     : {res.iterations}")
    print(f"procrustes error: {float(err):.2e}")
    assert float(err) < 5e-2


if __name__ == "__main__":
    main()
