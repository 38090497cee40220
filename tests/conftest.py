"""Test harness: force an 8-device CPU mesh so every parallelism strategy is
exercised without TPU hardware (SURVEY.md §4: jax's virtual multi-device
host replaces the reference's multi-process NCCL test rigs)."""

import os

# both are read when jax first initialises a backend, so they are set
# here, before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Persistent compile cache: the suite is compile-bound and every run
# recompiles identical tiny programs.  Where JAX_COMPILATION_CACHE_DIR is
# set, jax reads it and the harness sets no other; otherwise one fixed
# directory under .pytest_cache (gitignored, and kept off the chip
# machine by .chiprunignore) so warm runs skip XLA compilation entirely.
# The driver's command passes `-p no:cacheprovider` (pytest's own cache
# plugin: this directory is jax's and is written all the same) and starts
# from a cold cache.  PR 27 readings of that command on the sandbox's eight
# cores, six xdist workers: 566-754 s cold (four runs), 531 s warm, 1,617
# passed (the run that PR 26's tree could not finish in 1,470 s).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "..", ".pytest_cache", "xla_cache")
    jax.config.update("jax_compilation_cache_dir",
                      os.path.normpath(_cache_dir))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# One limit for every test: setup, call and teardown together.  A test that
# waits past SOFT_LIMIT_S fails where it waits (SIGALRM raises in the main
# thread, after every thread's stack went to stderr).  A wait inside a C
# call that no Python handler can interrupt is cut at HARD_LIMIT_S by
# faulthandler's watchdog thread, which dumps the stacks and exits the
# process: xdist reports the test as failed and starts a new worker.
# Readings (PR 27, six workers, cold compile cache, three whole runs): the
# slowest tests are the elastic grow and shrink end-to-ends, 58-74 s of
# waiting on elastic timeouts by design, then compile-bound model tests at
# 45-62 s.  The soft limit is three times the slowest and a margin for a
# loaded host (the same test read 137 s and 300 s in two runs of one tree
# while it still compiled op by op); the hard one leaves a failed test's
# teardown 30 s.
SOFT_LIMIT_S = 240.0
HARD_LIMIT_S = 270.0


def pytest_configure(config):
    # the watchdog writes when the process is past helping itself: give it
    # the process's own stderr, not the file pytest's capture swaps in
    capman = config.pluginmanager.getplugin("capturemanager")
    with capman.global_and_fixture_disabled():
        config._real_stderr = os.fdopen(os.dup(2), "w")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{item.nodeid} ran into the limit of "
                    f"{SOFT_LIMIT_S:g} s that every test has "
                    "(tests/conftest.py)")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, SOFT_LIMIT_S)
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True,
                                      file=item.config._real_stderr)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def smoke():
    """chip_smoke.py (repo root) as a module: its phases and builders."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt
    pt.seed(1234)
    yield
