"""The entry refuses what it cannot measure, and prints no result."""
import os
import shutil
import subprocess
import sys

import tpu_bench_helpers as h

ARGS = ["--workload", "minitron-4b.chat-b64", "--seed", "3000000123",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmarks/tpu/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_backend_that_is_not_a_tpu():
    r = _run(h.REPO, {})
    assert r.returncode == 1, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(h.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(h.REPO, "benchmarks", "tpu"),
                    tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
