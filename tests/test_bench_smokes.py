"""CI's benchmark smokes that assert behaviour, not time: the serving
rate sweep and the sparse absorb smoke on both backends, and the
checkpoint-payload assertions of ``benchmarks/run.py --only pipeline``.

Local smokes run in this process with ``BENCH_<date>.json`` sent to a
temporary directory; mesh smokes run as their own process (8 fake CPU
devices) from a copy of ``benchmarks/`` there, so their file lands there.
"""
import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

SMOKES = {
    "serving-local": ["bench_serving.py", "--smoke"],
    "serving-mesh": ["bench_serving.py", "--smoke", "--backend", "mesh"],
    "sparse-absorb-local": ["bench_serving.py", "--smoke", "--absorb",
                            "--regime", "sparse"],
    "sparse-absorb-mesh": ["bench_serving.py", "--smoke", "--absorb",
                           "--regime", "sparse", "--backend", "mesh"],
    "pipeline-payloads": ["run.py", "--only", "pipeline"],
}
# a row each smoke writes only after its assertions passed
_ROW = {
    "serving-local": "serving_dense_local_rate0",
    "serving-mesh": "serving_dense_mesh_rate0",
    "sparse-absorb-local": "serving_sparse_absorb_local",
    "sparse-absorb-mesh": "serving_sparse_absorb_mesh",
    "pipeline-payloads": "pipeline_apsp2_col_intermediates",
}


@pytest.fixture
def process_state():
    """The smokes are scripts: they turn the persistent compile cache on
    and the sparse one pins ``REPRO_DENSE_BYTES``.  Put both back for the
    tests that share this process."""
    import jax

    env = dict(os.environ)
    cache_dir = jax.config.jax_compilation_cache_dir
    yield
    os.environ.clear()
    os.environ.update(env)
    jax.config.update("jax_compilation_cache_dir", cache_dir)


@pytest.mark.parametrize("name", sorted(SMOKES))
def test_ci_bench_smoke(name, tmp_path, monkeypatch, process_state):
    script, *args = SMOKES[name]
    if "mesh" not in args:
        import benchmarks_path_helper  # noqa: F401  (adds benchmarks/)
        import run

        monkeypatch.setattr(
            run, "bench_json_path", lambda: str(tmp_path / "BENCH_test.json")
        )
        monkeypatch.chdir(tmp_path)
        if script == "run.py":
            monkeypatch.setattr(sys, "argv", [script, *args])
            run.main()
        else:
            import bench_serving

            bench_serving.main(args)
    else:
        bench = tmp_path / "benchmarks"
        bench.mkdir()
        for f in ("run.py", "bench_serving.py"):
            shutil.copy(os.path.join(REPO, "benchmarks", f), bench / f)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, str(bench / script), *args],
            capture_output=True, text=True, cwd=tmp_path, timeout=300,
            env=env,
        )
        assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    (path,) = glob.glob(str(tmp_path / "BENCH_*.json"))
    with open(path) as f:
        rows = {r["name"] for r in json.load(f)["rows"]}
    assert _ROW[name] in rows, sorted(rows)
