"""The limit that tests/conftest.py gives every test, shown on a run of
its own: two tests under that conftest's hooks with the two constants cut
to seconds, the first of which waits past them."""

import os
import subprocess
import sys
import textwrap

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))

CONFTEST = f"""
import importlib.util
spec = importlib.util.spec_from_file_location(
    "repo_conftest", {os.path.join(TESTS, "conftest.py")!r})
repo = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo)
repo.SOFT_LIMIT_S, repo.HARD_LIMIT_S = 1.0, 3.0
pytest_configure = repo.pytest_configure
pytest_runtest_protocol = repo.pytest_runtest_protocol
"""

WAITS = {
    # a wait Python can interrupt: the alarm fails the test where it waits
    "soft": "time.sleep(60)",
    # one it cannot (what a join inside a C call is to a handler): the
    # watchdog ends the worker and xdist starts another for the next test
    "hard": "signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})\n"
            "    time.sleep(60)",
}


@pytest.mark.parametrize("limit", ["soft", "hard"])
def test_waiting_test_fails_with_stacks_and_the_next_runs(limit, tmp_path):
    (tmp_path / "conftest.py").write_text(CONFTEST)
    (tmp_path / "test_two.py").write_text(textwrap.dedent("""\
        import signal
        import time

        def test_waits():
            {wait}

        def test_next():
            pass
        """).format(wait=WAITS[limit]))
    xdist = ["-p", "xdist", "-n", "1"] if limit == "hard" else []
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "test_two.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", *xdist],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "(most recent call first)" in out, out      # the stacks
    assert " in test_waits" in out, out
    if limit == "soft":
        assert "test_two.py::test_waits ran into the limit of 1 s" in out
    else:
        assert "crashed while running 'test_two.py::test_waits'" in out
