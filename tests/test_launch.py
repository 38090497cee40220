"""Launcher tests: TCPStore protocol, rendezvous, pod lifecycle, CLI
end-to-end on localhost, elastic restart, spawn.

Mirrors the reference pattern (SURVEY §4: multi-node logic tested by
env-faking the rendezvous on localhost)."""

import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

pytestmark = pytest.mark.cluster  # OS-process e2e: excluded by -m "not cluster"

from paddle_tpu.launch import (CollectiveController, Context, TCPStore,
                               parse_args)
from paddle_tpu.launch.elastic import ElasticManager
from paddle_tpu.launch.job import Container
from paddle_tpu.launch.master import Master
from paddle_tpu.launch.store import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(params=[True, False], ids=["native", "python"])
def native(request):
    """Which store server a test hosts: the C++ one or socketserver's."""
    if request.param:
        from paddle_tpu import runtime_native
        if not runtime_native.available():
            pytest.skip("libpdtpu_native.so is not built")
    return request.param


class TestTCPStore:
    def test_set_get_add_delete(self):
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        try:
            assert s.get("k") is None
            s.set("k", b"v")
            assert s.get("k") == b"v"
            assert s.add("n", 3) == 3
            assert s.add("n", 2) == 5
            assert s.delete("k") and not s.delete("k")
            assert s.keys("") == ["n"]
        finally:
            s.close()

    def test_per_call_timeout_override(self):
        """set/get take a per-call timeout= (KV-page transfer chunks
        need a longer deadline than heartbeats — serving/disagg.py):
        the override lands on the client socket for exactly that call
        and the store's default deadline is restored afterwards."""
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True,
                     timeout=30.0)
        applied = []

        class _Spy:
            def __init__(self, sock):
                self._sock = sock

            def settimeout(self, v):
                applied.append(v)
                self._sock.settimeout(v)

            def __getattr__(self, name):
                return getattr(self._sock, name)

        s._sock = _Spy(s._sock)
        try:
            s.set("big", b"x" * 4096, timeout=75.0)
            assert applied == [75.0, 30.0]          # applied + restored
            assert s._sock.gettimeout() == 30.0
            del applied[:]
            assert s.get("big", timeout=75.0) == b"x" * 4096
            assert applied == [75.0, 30.0]
            del applied[:]
            # no override → the socket deadline is never touched
            assert s.get("big") == b"x" * 4096
            assert applied == []
        finally:
            s.close()

    def test_wait_and_two_clients(self):
        master = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        client = TCPStore(master.endpoint)
        try:
            def setter():
                time.sleep(0.2)
                client.set("late", b"x")
            t = threading.Thread(target=setter)
            t.start()
            assert master.wait("late", timeout=5) == b"x"
            t.join()
            with pytest.raises(TimeoutError):
                master.wait("never", timeout=0.2)
        finally:
            client.close()
            master.close()

    def test_compare_set(self):
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        try:
            assert s.compare_set("c", b"", b"1")        # create-if-absent
            assert not s.compare_set("c", b"0", b"2")   # wrong expect
            assert s.compare_set("c", b"1", b"2")
            assert s.get("c") == b"2"
        finally:
            s.close()

    def test_reconnect_with_backoff_after_socket_death(self):
        """A bounced controller kills every client socket.  With a
        ``retry`` policy configured, add/compare_set/keys/delete
        transparently reconnect-and-retry (serving workers must cost a
        controller restart one retry, not their lease)."""
        from paddle_tpu.resilience.retry import RetryPolicy
        master = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        client = TCPStore(master.endpoint,
                          retry=RetryPolicy(max_attempts=4,
                                            backoff_s=0.001))
        try:
            client.set("n", b"v")
            for op in (lambda: client.add("ctr", 1),
                       lambda: client.compare_set("c", b"", b"1"),
                       lambda: client.keys(""),
                       lambda: client.delete("n")):
                dead = client._sock
                dead.close()        # the restart: next send dies
                op()                # reconnects under the policy
                assert client._sock is not dead
            assert client.get("c") == b"1"
            assert client.add("ctr", 1) == 2
            assert client.get("n") is None      # the delete applied
        finally:
            client.close()
            master.close()

    def test_reconnect_stress_loses_no_reply(self, native):
        """The scenario above as a stress: six clients of one master,
        each killing its socket before every op, so every op ends one
        server worker and starts another while five neighbours do the
        same.  Each client's socket timeout is 2 s, so a request the
        server never answers is a failure naming the op within seconds
        (four attempts), not a minute's wait per attempt."""
        from paddle_tpu.resilience.retry import RetryPolicy
        master = TCPStore(f"127.0.0.1:{free_port()}", is_master=True,
                          native=native)
        lost = []

        def churn(i):
            c = TCPStore(master.endpoint, timeout=2.0,
                         retry=RetryPolicy(max_attempts=4, backoff_s=0.001))
            ops = {"add": lambda: c.add(f"ctr{i}", 1),
                   "compare_set": lambda: c.compare_set(f"c{i}", b"", b"1"),
                   "keys": lambda: c.keys(f"c{i}"),
                   "delete": lambda: c.delete(f"c{i}")}
            try:
                for k in range(50):
                    for name, op in ops.items():
                        c._sock.close()     # the restart: next send dies
                        op()
                assert c.add(f"ctr{i}", 0) >= 50
            except Exception as e:  # noqa: BLE001
                lost.append(f"client {i}, round {k}, {name}: {e!r}")
            finally:
                c._sock.close()

        ts = [threading.Thread(target=churn, args=(i,)) for i in range(6)]
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)     # more interleavings of the clients
        try:
            [t.start() for t in ts]
            [t.join(timeout=120) for t in ts]
        finally:
            sys.setswitchinterval(switch)
        assert not lost and not any(t.is_alive() for t in ts), lost
        master.close()      # left open on failure: a wedged server's
        #                     close() would wait instead of reporting

    def test_close_hangs_up_on_a_connected_client(self, native):
        """A stopped server answers nobody and waits for nobody: with a
        client still connected (and a few workers already come and
        gone), ``close()`` returns within a second and the client's
        next op fails at once instead of being served by a leftover
        worker."""
        from paddle_tpu.resilience.retry import RetryPolicy
        master = TCPStore(f"127.0.0.1:{free_port()}", is_master=True,
                          native=native)
        client = TCPStore(master.endpoint, timeout=2.0)
        churn = TCPStore(master.endpoint, timeout=2.0,
                         retry=RetryPolicy(max_attempts=2, backoff_s=0.001))
        try:
            for _ in range(8):
                churn._sock.close()
                churn.set("k", b"v")
            assert client.get("k") == b"v"
            t0 = time.monotonic()
            master.close()
            assert time.monotonic() - t0 < 1.0
            assert client._sock.recv(1) == b""      # hung up, at once
            assert time.monotonic() - t0 < 1.0
        finally:
            churn._sock.close()
            client._sock.close()

    def test_accept_loop_survives_connections_reset_before_accept(
            self, native):
        """A peer that connects and resets while its connection still
        waits in the backlog must cost the server nothing: the accept
        loop goes on and the next client is served."""
        import socket
        import struct
        master = TCPStore(f"127.0.0.1:{free_port()}", is_master=True,
                          native=native)
        host, port = master.endpoint.rsplit(":", 1)
        try:
            t0 = time.monotonic()
            for _ in range(64):
                s = socket.create_connection((host, int(port)), timeout=2.0)
                s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))    # close() sends RST
                s.close()
            # a full backlog drops the SYN and the client waits 1 s for
            # its retransmit: the Python server's backlog of 5 cost 10 s
            assert time.monotonic() - t0 < 5.0
            late = TCPStore(master.endpoint, timeout=2.0)
            late.set("k", b"v")
            assert late.get("k") == b"v"
            late.close()
        finally:
            master.close()

    def test_no_retry_policy_still_surfaces_socket_death(self):
        """Without a policy the store keeps its fail-fast contract —
        the reconnect-with-backoff behaviour is strictly opt-in."""
        master = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        client = TCPStore(master.endpoint)
        try:
            client._sock.close()
            with pytest.raises(OSError):
                client.add("ctr", 1)
        finally:
            client.close()
            master.close()

    def test_compare_set_ghost_write_is_idempotent(self):
        """A CAS whose reply died with its socket may have applied
        server-side; the retried attempt then sees expect-mismatch with
        the key already holding OUR value.  That reads as success —
        lease renewal chains CAS on the previous value, so a ghost
        write must not drop the lease."""
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        try:
            # server state after the ghost write: v1 -> v2 applied,
            # reply lost; the client retries the same CAS
            s.set("lease", b"v2")
            assert s.compare_set("lease", b"v1", b"v2")
            # a genuine conflict (someone ELSE's value) still fails
            assert not s.compare_set("lease", b"v1", b"v3")
        finally:
            s.close()

    def test_injected_store_faults_retried_under_policy(self):
        """Chaos plans on ``store.set``/``store.get`` cover the cluster
        write ops (add/delete/cas map to set; keys maps to get) and are
        absorbed by the client retry policy."""
        from paddle_tpu import resilience as rs
        from paddle_tpu.resilience.retry import RetryPolicy
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True,
                     retry=RetryPolicy(max_attempts=4, backoff_s=0.001))
        inj = rs.install_faults(
            "store.set@0x2:ConnectionError;store.get@0:ConnectionError")
        try:
            assert s.add("ctr", 1) == 1
            assert s.keys("") == ["ctr"]
            assert ("store.set", 0) in inj.fired
            assert ("store.get", 0) in inj.fired
        finally:
            rs.clear_faults()
            s.close()

    def test_barrier(self):
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        c = TCPStore(s.endpoint)
        errs = []
        def one(store):
            try:
                store.barrier("b1", 2, timeout=5)
            except Exception as e:  # noqa: BLE001
                errs.append(e)
        try:
            ts = [threading.Thread(target=one, args=(x,)) for x in (s, c)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            assert not errs
        finally:
            c.close()
            s.close()


class TestRendezvous:
    def test_two_node_rank_assignment(self):
        port = free_port()
        results = {}

        def node(rank_hint, is_first):
            ctx = Context(nnodes=2, master=f"127.0.0.1:{port}",
                          rank=-1, job_id="t2n")
            # second node must not host the store
            if not is_first:
                ctx.rank = -1
            m = Master.__new__(Master)
            m.ctx = ctx
            m.generation = 0
            m.store = TCPStore(f"127.0.0.1:{port}", is_master=is_first,
                               timeout=10)
            try:
                r, eps = m.rendezvous()
                results[rank_hint] = (r, eps)
            finally:
                # the first node hosts the store: it must outlive the
                # second node's last read (a closing server hangs up on
                # whoever is still connected, reply sent or not)
                done.wait(timeout=15)
                m.store.close()

        done = threading.Barrier(2)
        t0 = threading.Thread(target=node, args=(0, True))
        t1 = threading.Thread(target=node, args=(1, False))
        t0.start(); time.sleep(0.1); t1.start()
        t0.join(); t1.join()
        ranks = sorted(r for r, _ in results.values())
        assert ranks == [0, 1]
        assert all(len(eps) == 2 for _, eps in results.values())


class TestContainer:
    def test_run_and_log(self, tmp_path):
        log = str(tmp_path / "w.log")
        c = Container(entrypoint=[sys.executable, "-c",
                                  "import os;print(os.environ['X_TEST'])"],
                      env={"X_TEST": "hello"}, log_path=log)
        c.start()
        while c.alive():
            time.sleep(0.02)
        assert c.returncode == 0
        c.terminate()
        assert "hello" in open(log).read()

    def test_terminate_kills_group(self, tmp_path):
        c = Container(entrypoint=[sys.executable, "-c",
                                  "import time;time.sleep(60)"],
                      env={}, log_path=str(tmp_path / "w.log"))
        c.start()
        assert c.alive()
        t0 = time.monotonic()
        c.terminate(grace=0.5)
        assert not c.alive()
        assert time.monotonic() - t0 < 10


def _write_script(tmp_path, body):
    p = tmp_path / "train.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


class TestCLI:
    def test_single_node_two_proc(self, tmp_path):
        script = _write_script(tmp_path, """
            import os
            rank = os.environ["PADDLE_TRAINER_ID"]
            world = os.environ["PADDLE_TRAINERS_NUM"]
            assert os.environ["PDTPU_PROCESS_ID"] == rank
            print(f"rank {rank} of {world} ok")
        """)
        log_dir = str(tmp_path / "log")
        ctx = parse_args(["--nproc_per_node", "2", "--log_dir", log_dir,
                          "--job_id", "cli1", script])
        assert CollectiveController(ctx).run() == 0
        logs = sorted(os.listdir(log_dir))
        assert logs == ["workerlog.0", "workerlog.1"]
        assert "rank 0 of 2 ok" in open(os.path.join(log_dir, "workerlog.0")).read()

    def test_failure_propagates(self, tmp_path):
        script = _write_script(tmp_path, """
            import os, sys
            sys.exit(3 if os.environ["PADDLE_TRAINER_ID"] == "1" else 0)
        """)
        ctx = parse_args(["--nproc_per_node", "2",
                          "--log_dir", str(tmp_path / "log"), script])
        assert CollectiveController(ctx).run() != 0

    def test_elastic_restart_recovers(self, tmp_path):
        # first generation fails, relaunch succeeds (marker file flips it)
        marker = tmp_path / "marker"
        script = _write_script(tmp_path, f"""
            import os, sys
            m = {str(repr(str(marker)))}
            if not os.path.exists(m):
                open(m, "w").close()
                sys.exit(1)
            print("recovered")
        """)
        ctx = parse_args(["--nproc_per_node", "1", "--elastic_level", "1",
                          "--max_restarts", "2",
                          "--log_dir", str(tmp_path / "log"), script])
        assert CollectiveController(ctx).run() == 0
        assert "recovered" in open(tmp_path / "log" / "workerlog.0").read()


class TestElasticManager:
    def test_corrupt_heartbeat_counts_as_dead(self):
        """An unparsable heartbeat payload (torn store write) must read
        as a dead node, not crash the liveness watcher every other
        node's recovery depends on."""
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        try:
            em = ElasticManager(s, "ejc", node_rank=0, nnodes=2,
                                timeout=0.3, heartbeat_period=0.1)
            em.start()
            s.set(em._key(1), b"not-a-float")
            time.sleep(0.5)   # past the startup grace period
            assert em.dead_nodes() == [1]
            em.stop()
        finally:
            s.close()

    def test_heartbeat_and_dead_detection(self):
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
        try:
            em = ElasticManager(s, "ej", node_rank=0, nnodes=2, timeout=0.5,
                                heartbeat_period=0.1)
            em.start()
            # inside the startup grace period an absent peer is NOT dead
            time.sleep(0.2)
            assert em.dead_nodes() == []
            # past the grace period node 1 (never heartbeats) is dead,
            # node 0 (own fresh heartbeat) is alive
            time.sleep(0.6)
            assert em.dead_nodes() == [1]
            em.stop()
        finally:
            s.close()


class TestSpawn:
    def test_spawn_single_inprocess(self):
        out = []
        from paddle_tpu.distributed import spawn
        spawn(lambda rank, x: out.append((rank, x)), args=(7,), nprocs=1)
        assert out == [(0, 7)]

    def test_spawn_multiproc(self, tmp_path):
        # run via subprocess to avoid importing jax state into forks
        script = _write_script(tmp_path, """
            import os
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            import sys
            sys.path.insert(0, os.environ["PDTPU_REPO"])
            from paddle_tpu.distributed.spawn import spawn

            def f(rank, base):
                assert os.environ["PADDLE_TRAINER_ID"] == str(rank)
                sys.exit(0 if rank + base >= 0 else 1)

            if __name__ == "__main__":
                spawn(f, args=(0,), nprocs=2)
                print("spawn-ok")
        """)
        env = {**os.environ, "PDTPU_REPO": REPO, "JAX_PLATFORMS": "cpu"}
        r = subprocess.run([sys.executable, script], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        assert "spawn-ok" in r.stdout


class TestPreemptionGuard:
    def test_sigterm_sets_flag_and_saves_once(self, tmp_path):
        import signal as sig
        from paddle_tpu.launch import PreemptionGuard

        saves = []
        marker = tmp_path / "ck"

        def save():
            saves.append(1)
            marker.write_text("saved")

        with PreemptionGuard(save_fn=save) as guard:
            assert not guard.preempted
            os.kill(os.getpid(), sig.SIGTERM)   # simulated preemption
            time.sleep(0.05)
            assert guard.preempted
        assert saves == [1] and marker.read_text() == "saved"
        # original handler restored: nothing blows up re-entering
        with PreemptionGuard() as g2:
            assert not g2.preempted

    def test_no_preemption_no_save(self):
        from paddle_tpu.launch import PreemptionGuard
        saves = []
        with PreemptionGuard(save_fn=lambda: saves.append(1)):
            pass
        assert saves == []

    def test_checkpoint_resume_roundtrip(self, tmp_path):
        """Preempt mid-training → save → resume from ckpt → loss continues
        falling (the §5.3 restart-based recovery contract)."""
        import signal as sig
        import jax.numpy as jnp
        import paddle_tpu as pt
        from paddle_tpu import nn
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.launch import PreemptionGuard
        from paddle_tpu.optimizer import AdamW

        pt.seed(0)

        def make_step():
            m = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 8))
            opt = AdamW(learning_rate=1e-2, parameters=m.parameters())
            return TrainStep(m, lambda mm, b: ((mm(b["x"]) - b["y"]) ** 2).mean(), opt)

        batch = {"x": jnp.ones((4, 8)), "y": jnp.zeros((4, 8))}
        path = str(tmp_path / "state")
        step = make_step()
        state = step.init_state()
        with PreemptionGuard(save_fn=lambda: pt.save(state, path)) as guard:
            for i in range(20):
                state, met = step(state, batch)
                if i == 5:
                    os.kill(os.getpid(), sig.SIGTERM)
                if guard.preempted:
                    break
        loss_at_preempt = float(met["loss"])

        # "relaunch": fresh step, load the saved state, keep training
        step2 = make_step()
        state2 = pt.load(path)
        # jax.random keys round-trip as raw key_data — rewrap on load
        import jax
        state2["rng"] = jax.random.wrap_key_data(
            jnp.asarray(jax.random.key_data(state["rng"])))
        for _ in range(10):
            state2, met2 = step2(state2, batch)
        assert float(met2["loss"]) < loss_at_preempt

    def test_raising_save_fn_still_restores_handlers(self):
        """A save_fn that raises on exit must not leave the SIGTERM
        handler installed forever on a dead guard."""
        import signal as sig
        from paddle_tpu.launch import PreemptionGuard

        prev = sig.getsignal(sig.SIGTERM)

        def boom():
            raise RuntimeError("ckpt write failed")

        with pytest.raises(RuntimeError, match="ckpt write failed"):
            with PreemptionGuard(save_fn=boom) as guard:
                os.kill(os.getpid(), sig.SIGTERM)
                time.sleep(0.05)
                assert guard.preempted
        assert sig.getsignal(sig.SIGTERM) is prev

    def test_guard_reusable_across_runs(self, tmp_path):
        import signal as sig
        from paddle_tpu.launch import PreemptionGuard
        saves = []
        guard = PreemptionGuard(save_fn=lambda: saves.append(1))
        for attempt in range(2):
            with guard:
                assert not guard.preempted   # stale flag must be cleared
                os.kill(os.getpid(), sig.SIGTERM)
                time.sleep(0.05)
                assert guard.preempted
        assert saves == [1, 1]               # saved on BOTH preemptions
