"""Elastic shrink-resume end to end across OS processes: contract and helpers in
tests/test_multiprocess_cluster.py, a file of its own for the scheduler's
sake (see there)."""

import os
import signal
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.cluster  # OS-process e2e: excluded by -m "not cluster"

from paddle_tpu.launch import CollectiveController, parse_args
from paddle_tpu.launch.store import free_port

from test_multiprocess_cluster import (REPO, WORKER, _read_records,
                                       _run_single_reference)


class TestElasticShrinkResume:
    STEPS = 10
    KILL_AFTER = 5  # kill node 1 once the step_5 checkpoint is complete

    def test_kill_node_shrink_world_resume_from_ckpt(self, tmp_path,
                                                     monkeypatch):
        out = str(tmp_path / "elastic.jsonl")
        ckpt_dir = str(tmp_path / "ckpt")
        port = free_port()
        master = f"127.0.0.1:{port}"

        monkeypatch.setenv("PDTPU_REPO", REPO)
        monkeypatch.setenv("PDTPU_TEST_DEVICES", "4")
        monkeypatch.setenv("PDTPU_TEST_STEPS", str(self.STEPS))
        monkeypatch.setenv("PDTPU_TEST_OUT", out)
        monkeypatch.setenv("PDTPU_TEST_CKPT_DIR", ckpt_dir)
        # node death: node B's worker (global rank 1) SIGKILLs itself right
        # after checkpointing step KILL_AFTER, and node B's controller gives
        # up (--max_restarts 0) — the node is gone, exactly like a host
        # failure mid-job
        monkeypatch.setenv("PDTPU_TEST_KILL_RANK", "1")
        monkeypatch.setenv("PDTPU_TEST_KILL_STEP", str(self.KILL_AFTER))

        env_b = {**os.environ, "PYTHONPATH": REPO}
        node_b = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.launch",
             "--nnodes", "1:2", "--rank", "1", "--master", master,
             "--nproc_per_node", "1", "--elastic_level", "1",
             "--elastic_timeout", "4", "--max_restarts", "0",
             "--job_id", "mpc2",
             "--log_dir", str(tmp_path / "log_b"), WORKER],
            env=env_b, cwd=REPO, start_new_session=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

        # node A: the surviving node, driven in the main thread (signal
        # handlers require it); hosts the rendezvous store (rank 0); its
        # worker must NOT kill itself (it is rank 0)
        ctx = parse_args(["--nnodes", "1:2", "--rank", "0",
                          "--master", master, "--nproc_per_node", "1",
                          "--elastic_level", "1", "--elastic_timeout", "4",
                          "--job_id", "mpc2",
                          "--log_dir", str(tmp_path / "log_a"), WORKER])
        try:
            rc = CollectiveController(ctx).run()
        finally:
            try:
                os.killpg(node_b.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            node_b.wait(timeout=30)

        assert rc == 0
        records = _read_records(out)
        # generation 0 died before rank 0 finished → only the resumed
        # (shrunk) generation reports
        final = records[-1]
        assert final["world"] == 1 and final["devices"] == 4
        assert final["resumed_from"] is not None
        # resumed from the kill-point checkpoint (or at worst one step
        # earlier, if the survivor was torn down mid-save)
        assert self.KILL_AFTER - 1 <= final["start"] <= self.KILL_AFTER

        single = _run_single_reference(tmp_path, self.STEPS)
        steps = sorted(int(s) for s in final["losses"])
        assert steps[-1] == self.STEPS - 1
        a = [final["losses"][str(i)] for i in steps]
        b = [single["losses"][str(i)] for i in steps]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


class TestElasticShrinkResumeSharded:
    """Shrink across a SHARDED (dp, sharding=2) ZeRO-2 topology: the
    relaunch must reshard-on-load partitioned optimizer moments (8-device
    (4,2) mesh -> 4-device (2,2) mesh), not just redistribute dp data."""

    STEPS = 10
    KILL_AFTER = 5

    def test_kill_node_shrink_sharded_state(self, tmp_path, monkeypatch):
        out = str(tmp_path / "elastic_sharded.jsonl")
        ckpt_dir = str(tmp_path / "ckpt")
        port = free_port()
        master = f"127.0.0.1:{port}"

        monkeypatch.setenv("PDTPU_REPO", REPO)
        monkeypatch.setenv("PDTPU_TEST_DEVICES", "4")
        monkeypatch.setenv("PDTPU_TEST_STEPS", str(self.STEPS))
        monkeypatch.setenv("PDTPU_TEST_OUT", out)
        monkeypatch.setenv("PDTPU_TEST_CKPT_DIR", ckpt_dir)
        monkeypatch.setenv("PDTPU_TEST_TOPO", "zero")
        monkeypatch.setenv("PDTPU_TEST_DIM", "64")
        monkeypatch.setenv("PDTPU_TEST_KILL_RANK", "1")
        monkeypatch.setenv("PDTPU_TEST_KILL_STEP", str(self.KILL_AFTER))

        env_b = {**os.environ, "PYTHONPATH": REPO}
        node_b = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.launch",
             "--nnodes", "1:2", "--rank", "1", "--master", master,
             "--nproc_per_node", "1", "--elastic_level", "1",
             "--elastic_timeout", "4", "--max_restarts", "0",
             "--job_id", "mpc4",
             "--log_dir", str(tmp_path / "log_b"), WORKER],
            env=env_b, cwd=REPO, start_new_session=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

        ctx = parse_args(["--nnodes", "1:2", "--rank", "0",
                          "--master", master, "--nproc_per_node", "1",
                          "--elastic_level", "1", "--elastic_timeout", "4",
                          "--job_id", "mpc4",
                          "--log_dir", str(tmp_path / "log_a"), WORKER])
        try:
            rc = CollectiveController(ctx).run()
        finally:
            try:
                os.killpg(node_b.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            node_b.wait(timeout=30)

        assert rc == 0
        final = _read_records(out)[-1]
        assert final["world"] == 1 and final["devices"] == 4
        assert final["resumed_from"] is not None
        assert self.KILL_AFTER - 1 <= final["start"] <= self.KILL_AFTER

        single = _run_single_reference(tmp_path, self.STEPS)
        steps = sorted(int(s) for s in final["losses"])
        assert steps[-1] == self.STEPS - 1
        a = [final["losses"][str(i)] for i in steps]
        b = [single["losses"][str(i)] for i in steps]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
