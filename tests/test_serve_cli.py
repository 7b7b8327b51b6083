"""The ``serve.py --manifold`` CLI end to end: each command's exit status
and printed summary.  Local commands run in this process through
``serve.main``; the mesh command runs in its own process with 8 fake CPU
devices."""
import os
import re
import subprocess
import sys

import jax
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# the README's serve commands and CI's stress commands (local and on a
# 2x2 mesh of fake CPU devices), at the CI smoke sizes
SERVE_COMMANDS = {
    "readme-default": [],
    "readme-sparse": ["--regime", "sparse", "--landmarks", "64"],
    "readme-stress": ["--objective", "stress"],
    "readme-replicas": ["--absorb", "8", "--replicas", "2"],
    "ci-stress": ["--objective", "stress", "--absorb", "16"],
    "ci-stress-mesh": ["--objective", "stress", "--absorb", "16",
                       "--mesh", "2x2"],
}
_SUMMARY = re.compile(
    r"^\[serve manifold\] regime=(\w+) objective=(\w+) fit=\S+s "
    r"serve=\S+s \(\d+ pts/s\) p50=\S+ms p99=\S+ms wait=\S+ms "
    r"service=\S+ms mean_batch=\S+ absorbed=(\d+) v(\d+) err=\S+ "
    r"rv=\S+$",
    re.M,
)


@pytest.fixture
def keep_compile_cache():
    """``serve.main`` turns the persistent compile cache on; put the
    setting back for the tests that share this process."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def _serve(argv, tmp_path, monkeypatch, capsys) -> str:
    """stdout of ``serve.py`` run with ``argv``; raises if it fails."""
    if "--mesh" not in argv:
        from repro.launch import serve

        monkeypatch.chdir(tmp_path)
        monkeypatch.setattr(sys, "argv", ["serve.py", *argv])
        serve.main()
        return capsys.readouterr().out
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "src/repro/launch/serve.py"),
         *argv],
        capture_output=True, text=True, cwd=tmp_path, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


@pytest.mark.parametrize("name", sorted(SERVE_COMMANDS))
def test_serve_manifold_cli(name, tmp_path, monkeypatch, capsys,
                            keep_compile_cache):
    extra = SERVE_COMMANDS[name]
    out = _serve(
        ["--manifold", "swissroll", "--smoke", "--n-base", "256",
         "--n-stream", "64", *extra],
        tmp_path, monkeypatch, capsys,
    )
    summary = _SUMMARY.findall(out)
    assert len(summary) == 1, out
    regime, objective, absorbed, version = summary[0]
    flag = dict(zip(extra[::2], extra[1::2]))
    assert regime == flag.get("--regime", "dense")
    assert objective == flag.get("--objective", "spectral")
    assert int(absorbed) == int(flag.get("--absorb", 0))
    assert int(version) == (1 if "--absorb" in flag else 0)
    replicas = re.findall(r"^  \[replica \S+\] requests=(\d+) .* "
                          r"lag=0 alive=True$", out, re.M)
    assert len(replicas) == int(flag.get("--replicas", 0))
    if replicas:
        assert sum(map(int, replicas)) == 64
