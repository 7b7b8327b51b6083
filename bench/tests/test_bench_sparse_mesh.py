"""The four-chip landmark cell (``fit.swissroll-sparse-mesh``) end to end
at a small n on four CPU devices: sound, it is correct; with one chip's
landmark shard left unsolved, it is not."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CELL = "fit.swissroll-sparse-mesh"

SCRIPT = """
import json, sys, types, jax, jax.numpy as jnp
from bench import generator, run
from repro.core.pipeline import MeshBackend
bench, cell, cfg, traffic = run.find_cell(%(cell)r)
cfg = dict(cfg, n=2048)
generator.validate(cell, cfg, traffic)
devs = jax.devices()[:cell["chips"]]
be = generator.backend(devs)
assert isinstance(be, MeshBackend), be
assert dict(be.mesh.shape) == {"data": 2, "model": 2}, be.mesh.shape
args = types.SimpleNamespace(seed=2**31 + 11, seconds=0.5, trace=0)
out = {"sound": run.measure_cell(args, bench, cell, cfg, traffic, devs)}

solve = MeshBackend.sparse_segment

def one_shard_unsolved(self, cfg, nbr, w, lm_idx, panel, lo, hi):
    # the last chip's rows of the panel stay at +inf, as if that chip
    # never ran its batches
    got = solve(self, cfg, nbr, w, lm_idx, panel, lo, hi)
    ml = lm_idx.shape[0] // self.landmark_multiple
    return got.at[-ml:].set(jnp.inf)

MeshBackend.sparse_segment = one_shard_unsolved
out["broken"] = run.measure_cell(args, bench, cell, cfg, traffic, devs)
print(json.dumps({k: {"correct": v["correct"], "checks": v["checks"],
                      "device": v["device"]} for k, v in out.items()}))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    res = subprocess.run([sys.executable, "-c", SCRIPT % {"cell": CELL}],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_the_sparse_mesh_cell_runs_correct_on_four_devices(runs):
    out = runs["sound"]
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]


def test_one_unsolved_landmark_shard_is_not_correct(runs):
    out = runs["broken"]
    assert not out["correct"], out["checks"]
    # the unsolved rows reach the checks: the panel rows and the chart
    # built from them are both far off
    assert out["checks"]["geo_gap"]["value"] > 1.0
    assert out["checks"]["emb_gap"]["value"] > \
        out["checks"]["emb_gap"]["limit"]


# ------------------------------------------------- frontier_chip_skew ----


def _skew_reader():
    import importlib.util

    path = os.path.join(ROOT, "bench", "metrics", "frontier_chip_skew.fit.py")
    spec = importlib.util.spec_from_file_location("m_frontier_chip_skew",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _skew_ctx(frontier_ns):
    """A reduced four-chip (or fewer) window of 1 s: chip i runs
    ``frontier_ns[i]`` ns of ``frontier_relax`` calls, in ten sweeps, and
    a gather fusion as long again that the metric must not count."""
    import types

    from bench import trace

    device = {}
    for chip, total in enumerate(frontier_ns):
        evs, t = [], 1000
        for _ in range(10):
            step = total // 10
            evs.append(["fusion", t, step, "%fusion.3 = f32[20,64,128]"])
            evs.append(["frontier_relax", t + step, step,
                        "%frontier_relax.1 = f32[64,128]"])
            t += 2 * step + 100
        device[str(chip)] = evs
    norm = {"host": [["bench:window", 0, 10**9]], "device": device}
    return types.SimpleNamespace(reduced=trace.reduce(norm))


@pytest.mark.parametrize("frontier_ns, want", [
    ([40_000_000] * 4, 0.0),                           # equal chips
    # one chip at 1.5x: mean 1.125 units, max 1.5 -> 1.5/1.125 - 1
    ([40_000_000] * 3 + [60_000_000], 100.0 * (1.5 / 1.125 - 1.0)),
    ([0, 0, 0, 40_000_000], 300.0),                   # one chip did all
])
def test_frontier_chip_skew_reads_the_busiest_chip_over_the_mean(
        frontier_ns, want):
    got = _skew_reader()(_skew_ctx(frontier_ns))
    assert got == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("frontier_ns", [[40_000_000], [0, 0, 0, 0]])
def test_frontier_chip_skew_has_nothing_to_read(frontier_ns):
    """One chip, or no frontier calls in the window: None."""
    assert _skew_reader()(_skew_ctx(frontier_ns)) is None
