"""Smoke tests: the examples/ scripts must run end-to-end on the CPU mesh
(tiny configs). Mirrors the reference's runnable-demo guarantee."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *argv],
        env=env, capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "done" in p.stdout
    return p.stdout


def test_train_llama_tiny():
    out = _run("train_llama.py", "--steps", "6", "--seq", "64", "--batch", "2")
    assert "loss=" in out


def test_train_llama_hybrid():
    out = _run("train_llama.py", "--steps", "4", "--seq", "64", "--batch",
               "4", "--dp", "2", "--mp", "2", "--sharding", "2")
    assert "loss=" in out


def test_train_moe_ep():
    out = _run("train_moe.py", "--steps", "4", "--seq", "32", "--ep", "2")
    assert "loss=" in out


def test_train_ps_ctr():
    out = _run("train_ps_ctr.py", "--steps", "30")
    assert "loss=" in out


def test_train_long_context_ring():
    out = _run("train_long_context.py", "--steps", "4", "--seq", "128",
               "--sep", "4", "--dp", "2")
    assert "loss=" in out and "sep=4" in out


def test_train_long_context_ulysses():
    out = _run("train_long_context.py", "--steps", "4", "--seq", "128",
               "--sep", "2", "--dp", "2", "--impl", "ulysses")
    assert "loss=" in out


@pytest.mark.parametrize("argv", [
    ("--algo", "weight_only_int8"),
    ("--algo", "weight_only_int4", "--mp", "2"),
])
def test_serve_quantized(argv):
    _run("serve_quantized.py", *argv)
