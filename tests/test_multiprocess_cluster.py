"""Multi-process cluster end-to-end tests.

The one code path a real multi-host TPU pod depends on that single-process
tests cannot reach: ``paddle_tpu.launch`` → per-process env protocol →
``init_parallel_env`` → ``jax.distributed.initialize`` → cross-process
collectives (gloo on CPU, ICI/DCN on TPU) → joint training.  SURVEY §4
patterns 2-3, §5.3, §5.8.

Three contracts (the elastic ones in test_multiprocess_elastic_shrink.py
and test_multiprocess_elastic_grow.py: xdist's ``--dist loadfile`` gives a
file to one worker and starts the files with few tests last, so the six
tests in one file, ~300 s of waiting on elastic timeouts, were the tail
of every run):
- cluster parity: 2 OS processes × 4 virtual CPU devices each train dp=8
  jointly and reproduce the single-process 8-device loss trajectory.
- elastic shrink-resume: kill one node mid-run → the surviving node detects
  the death, relaunches at a smaller world size, resumes from the sharded
  checkpoint via reshard-on-load, and the continued trajectory matches an
  uninterrupted reference run.
- elastic grow-resume: a node joins a HEALTHY below-MAX job mid-run → the
  running cluster sees the join request, advances the shared rendezvous
  round, relaunches at the larger world, and resumes from the latest
  checkpoint with the trajectory again matching the reference run
  (reference: fleet elastic manager relaunches on ANY membership change,
  node-join included — SURVEY §2.7, §5.3).
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.cluster  # OS-process e2e: excluded by -m "not cluster"

from paddle_tpu.launch import CollectiveController, parse_args
from paddle_tpu.launch.store import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "cluster_worker.py")


def _read_records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _run_single_reference(tmp_path, steps):
    """Uninterrupted single-process 8-device run of the same training."""
    out = str(tmp_path / "single.jsonl")
    env = {**os.environ, "PDTPU_REPO": REPO, "PDTPU_TEST_DEVICES": "8",
           "PDTPU_TEST_STEPS": str(steps), "PDTPU_TEST_OUT": out}
    for k in ("PDTPU_COORDINATOR", "PDTPU_TEST_CKPT_DIR",
              "PDTPU_TEST_KILL_RANK", "PDTPU_TEST_KILL_STEP",
              "PDTPU_TEST_STEP_SLEEP"):
        env.pop(k, None)
    r = subprocess.run([sys.executable, WORKER], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    (rec,) = _read_records(out)
    return rec


class TestClusterParity:
    STEPS = 8

    def test_two_processes_match_single_process(self, tmp_path, monkeypatch):
        out = str(tmp_path / "cluster.jsonl")
        monkeypatch.setenv("PDTPU_REPO", REPO)
        monkeypatch.setenv("PDTPU_TEST_DEVICES", "4")
        monkeypatch.setenv("PDTPU_TEST_STEPS", str(self.STEPS))
        monkeypatch.setenv("PDTPU_TEST_OUT", out)
        monkeypatch.delenv("PDTPU_TEST_CKPT_DIR", raising=False)

        ctx = parse_args(["--nproc_per_node", "2", "--job_id", "mpc1",
                          "--log_dir", str(tmp_path / "log"), WORKER])
        assert CollectiveController(ctx).run() == 0

        (cluster,) = _read_records(out)
        assert cluster["world"] == 2 and cluster["devices"] == 8
        single = _run_single_reference(tmp_path, self.STEPS)
        a = [cluster["losses"][str(i)] for i in range(self.STEPS)]
        b = [single["losses"][str(i)] for i in range(self.STEPS)]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


class TestClusterServing:
    """Cluster serving control plane across real OS processes: per-host
    ``python -m paddle_tpu.serving.worker`` loops over a shared
    TCPStore, an in-test ``ClusterController``, and the full failure
    menu in one fleet lifetime — SIGKILL a decode worker mid-churn
    (lease-expiry evacuation), SIGTERM a prefill worker (PreemptionGuard
    graceful drain), then command-driven drain of the rest — with every
    batch greedy token-identical to a colocated single-engine reference
    and every worker's exit report showing zero compiles after warmup
    and a fully reclaimed KV pool."""

    ROLES = ("prefill", "prefill", "decode", "decode")

    def _env(self):
        # the workers share the harness's compile cache (conftest.py):
        # the variable where it is set, else the one fixed directory
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.path.join(REPO, ".pytest_cache", "xla_cache")
        env = {**os.environ,
               "PDTPU_REPO": REPO,
               "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "JAX_COMPILATION_CACHE_DIR": cache,
               "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
               "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
               "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
               # every persistent-cache hit logs two ~2 KB
               # cpu_aot_loader error lines on some hosts; the workers'
               # stderr is a pipe nobody reads until they exit, and a
               # warm cache filled it (64 KiB) before a worker came up
               "TF_CPP_MIN_LOG_LEVEL": "3"}
        env.pop("PDTPU_FAULTS", None)
        return env

    def _spawn(self, endpoint, wid, role, env):
        return subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.worker",
             "--store", endpoint, "--role", role,
             "--factory", WORKER + ":make_serving_engine",
             "--worker-id", wid, "--lease-deadline-s", "6",
             "--status-interval-s", "0.05", "--steps-per-poll", "2",
             "--seed", "0"],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)

    @staticmethod
    def _assert_alive(procs, may_exit=()):
        for wid, p in procs.items():
            if wid not in may_exit and p.poll() is not None:
                out, err = p.communicate(timeout=10)
                raise AssertionError(
                    f"{wid} died rc={p.returncode}\n{out}\n{err}")

    def _pump_until(self, ctl, procs, rids, *, timeout_s, may_exit=()):
        import time
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            ctl.pump()
            if all(r in ctl.outputs for r in rids):
                return
            self._assert_alive(procs, may_exit)
            time.sleep(0.01)
        missing = [r for r in rids if r not in ctl.outputs]
        raise AssertionError(f"undelivered after {timeout_s}s: {missing}")

    @staticmethod
    def _report(proc, *, timeout=90):
        out, err = proc.communicate(timeout=timeout)
        assert proc.returncode == 0, f"rc={proc.returncode}\n{out}\n{err}"
        lines = [ln for ln in out.splitlines() if ln.strip()]
        assert lines, f"no report on stdout\n{err}"
        return json.loads(lines[-1])

    def test_fleet_kill_sigterm_drain_token_identity(self, tmp_path):
        import time

        import paddle_tpu as pt
        from paddle_tpu import serving
        from paddle_tpu.launch.store import TCPStore
        from paddle_tpu.models.llama import llama

        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, size=n).astype(np.int32)
                   for n in (5, 17, 9, 26)]
        pt.seed(0)
        ref_eng = serving.Engine(llama("tiny"), max_batch=2,
                                 max_seq_len=64, page_size=8,
                                 prefill_chunk=8).warmup()
        ref_rids = [ref_eng.add_request(p, max_new_tokens=8)
                    for p in prompts]
        ref_outs = ref_eng.run()
        ref = [ref_outs[r] for r in ref_rids]
        ref_rids = [ref_eng.add_request(p, max_new_tokens=24)
                    for p in prompts]
        ref_outs = ref_eng.run()
        ref24 = [ref_outs[r] for r in ref_rids]

        env = self._env()
        store = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        procs = {}
        try:
            for i, role in enumerate(self.ROLES):
                wid = f"w{i}-{role}"
                procs[wid] = self._spawn(store.endpoint, wid, role, env)
            ctl = serving.ClusterController(store, lease_deadline_s=6.0)
            # a worker registers 10 s after its start on a cold compile
            # cache (PR 27); one that has not after 90 s will not
            deadline = time.time() + 90
            while True:
                self._assert_alive(procs)
                try:
                    ctl.wait_for_workers(4, timeout_s=2.0)
                    break
                except TimeoutError:
                    if time.time() < deadline:
                        continue
                    for p in procs.values():
                        p.kill()
                    raise AssertionError(
                        "four workers never registered\n" + "\n".join(
                            f"{wid} stderr:\n{p.communicate()[1][-2000:]}"
                            for wid, p in procs.items())) from None

            # phase 1: disagg fleet serves token-identically
            rids = [ctl.submit(p, max_new_tokens=8) for p in prompts]
            self._pump_until(ctl, procs, rids, timeout_s=60)
            assert [ctl.outputs[r]["tokens"] for r in rids] == ref

            # phase 2: SIGKILL a decode worker the moment it owns an
            # uncollected assignment (waves of long decodes keep the
            # tier busy — a fixed batch outruns the poll on this tiny
            # model); lease-expiry evacuation re-delivers every wave
            # token-identically
            victim, rids = None, []
            deadline = time.time() + 60
            while victim is None and time.time() < deadline:
                rids += [ctl.submit(p, max_new_tokens=24)
                         for p in prompts]
                wave_end = time.time() + 5
                while victim is None and time.time() < wave_end:
                    ctl.pump()
                    for r in rids:
                        a = ctl._assigned.get(r)
                        if r not in ctl.outputs and a \
                                and a["wid"].endswith("decode"):
                            victim = a["wid"]
                            break
            assert victim, "no decode worker ever owned an assignment"
            procs[victim].kill()
            self._pump_until(ctl, procs, rids, timeout_s=60,
                             may_exit=(victim,))
            for i, r in enumerate(rids):
                assert ctl.outputs[r]["tokens"] == ref24[i % len(ref24)]
            assert ctl.members()[victim]["state"] == "dead"
            survivor = {"w2-decode": "w3-decode",
                        "w3-decode": "w2-decode"}[victim]

            # phase 3: SIGTERM a prefill worker mid-batch — graceful
            # drain hands off, deregisters, exits 0 with a clean report
            rids = [ctl.submit(p, max_new_tokens=8) for p in prompts]
            for _ in range(5):
                ctl.pump()
                time.sleep(0.01)
            procs["w1-prefill"].send_signal(signal.SIGTERM)
            self._pump_until(ctl, procs, rids, timeout_s=60,
                             may_exit=(victim, "w1-prefill"))
            assert [ctl.outputs[r]["tokens"] for r in rids] == ref
            rep = self._report(procs["w1-prefill"])
            assert rep["free_blocks"] == rep["num_blocks"]
            assert rep["compiles_after_warmup"] == 0
            assert ctl.members()["w1-prefill"]["state"] == "left"

            # phase 4: command-driven drain of the survivors
            for wid in ("w0-prefill", survivor):
                ctl.drain_worker(wid)
            for wid in ("w0-prefill", survivor):
                rep = self._report(procs[wid])
                assert rep["free_blocks"] == rep["num_blocks"]
                assert rep["compiles_after_warmup"] == 0
                assert rep["lease_losses"] == 0
                assert ctl.members()[wid]["state"] == "left"
        finally:
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            store.close()
