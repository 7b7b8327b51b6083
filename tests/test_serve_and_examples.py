"""The example scripts: those on one device run in this process, the
distributed one in its own process with 8 fake CPU devices."""
import importlib
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.parametrize(
    "script",
    ["quickstart.py", "swissroll_end_to_end.py", "emnist_manifold.py"],
)
def test_quickstart_example_runs(script, tmp_path):
    if script != "swissroll_end_to_end.py":
        import examples_path_helper  # noqa: F401  (adds examples/ to sys.path)

        importlib.import_module(script[:-3]).main()
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)  # the script asks for its 8 devices itself
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, cwd=tmp_path, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
