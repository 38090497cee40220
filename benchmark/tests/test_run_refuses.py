"""Without a TPU ``run.py`` fails and prints no result line; so it does in
a directory that holds only BENCHMARK.json and the benchmark's files."""

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "mistral-7b.train-8k", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py")] + ARGS,
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
