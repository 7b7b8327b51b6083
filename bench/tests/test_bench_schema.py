"""BENCHMARK.json against the benchmark's contract, and discovery of
every configuration, traffic mix and metric reader by name."""
import importlib.util
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_names_units_and_entry_keys(bench):
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    assert len(names) == len(bench["configs"])
    cells = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert line(w["why"])
        cells.add(w["name"])
    assert len(cells) == len(bench["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(bench["workloads"])
    assert {w["config"] for w in bench["workloads"]} == names
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in SOURCES_E2E
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    def reports(m, cell):
        return cell in m.get("workloads", [cell])

    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"] if reports(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(reports(m, w["name"]) for m in bench["per_layer"])
    # a per-layer metric's cells all report the metric it moves
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", [w["name"] for w in
                                        bench["workloads"]]):
            assert reports(moved, cell), (m["name"], cell)


def test_configs_traffic_and_readers_are_found_by_name(bench):
    from bench import run

    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert cfg["source"] and line(cfg["source"])
    for w in bench["workloads"]:
        _, cell, cfg, traffic = run.find_cell(w["name"])
        assert traffic["kind"] in ("closed_fits", "open_reads")
        assert cfg["n"] > 0
    for m in bench["per_layer"]:
        assert callable(run.load_reader(m["name"]))
        path = os.path.join(ROOT, "bench", "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("m", path)
        assert spec is not None


def test_bounds_fit_the_check_budget(bench):
    cells_max = 24
    runs = 2 + 14 * cells_max
    need = runs * (bench["run_seconds"] + 60) + cells_max * 180 + 1200
    assert need <= 43200


def test_every_key_the_harness_says_it_reads_is_read():
    import inspect

    from bench import data, generator

    # the code, less the tables that name the keys
    src = inspect.getsource(generator) + inspect.getsource(data)
    for table in ("CONFIG_READ = {", "TRAFFIC_KEYS = {"):
        i = src.index(table)
        src = src[:i] + src[src.index("}", i) + 1:]
    for key in generator.CONFIG_READ:
        assert f'cfg["{key}"]' in src or f'("{key}",' in src, key
    for kind, keys in generator.TRAFFIC_KEYS.items():
        for key in keys - {"kind", "why"}:
            assert f'tr["{key}"]' in src or f'traffic["{key}"]' in src, key


def _refused(case):
    """The dense configuration and the reads traffic, changed by one
    thing nothing in the harness would run as stated."""
    from bench import run

    _, cell, cfg, traffic = run.find_cell("serve.swissroll-dense-over")
    cell, cfg, traffic = dict(cell), dict(cfg), dict(traffic)
    kind, key, value = case
    {"cell": cell, "cfg": cfg, "traffic": traffic}[kind][key] = value
    return cell, cfg, traffic


@pytest.mark.parametrize("case", [
    ("cfg", "mesh_shape", [2, 2]),                 # a key nothing reads
    ("cfg", "dataset", "synthetic_emnist"),        # a data set not known
    ("cfg", "precision", "bfloat16"),              # a precision not run
    ("cfg", "regime", "sparse"),                   # no sparse mapper
    ("cell", "chips", 4),                          # reads on one chip only
    ("traffic", "burst", {"factor": 3}),           # a key nothing reads
    ("traffic", "size", {"dist": "uniform", "mean": 4, "max": 64}),
])
def test_files_that_name_what_the_harness_cannot_run_are_refused(case):
    from bench import generator

    with pytest.raises(ValueError):
        generator.validate(*_refused(case))


def test_a_four_chip_fit_cell_runs_on_a_two_by_two_mesh():
    """A fit cell that asks for four chips runs ``MeshBackend`` over a
    (2, 2) mesh, end to end and correct (four CPU devices, small n)."""
    import subprocess
    import sys

    script = """
import json, types, jax
from bench import generator, run
from repro.core.pipeline import MeshBackend
bench, cell, cfg, traffic = run.find_cell("fit.swissroll-dense")
cell = dict(cell, name="fit.mesh", chips=4)
cfg = dict(cfg, n=1024)
generator.validate(cell, cfg, traffic)
devs = jax.devices()[:4]
be = generator.backend(devs)
assert isinstance(be, MeshBackend) and dict(be.mesh.shape) == {"data": 2, "model": 2}
args = types.SimpleNamespace(seed=2**31 + 9, seconds=0.5, trace=0)
out = run.measure_cell(args, bench, cell, cfg, traffic, devs)
print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    res = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
