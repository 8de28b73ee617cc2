"""run.py ends non-zero with no result where JAX finds no TPU, and in a
directory that holds only BENCHMARK.json and the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELL = ["--workload", json.load(f)["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmark/run.py", *CELL], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu():
    r = run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU device" in r.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
