"""Job / Pod / Container process model.

Reference: python/paddle/distributed/launch/job/pod.py, job/container.py —
a Pod is the per-node set of Containers; a Container is one training
subprocess with injected env + redirected logs.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional


@dataclasses.dataclass
class Container:
    """One training subprocess with env injection and log redirection."""

    entrypoint: List[str]
    env: Dict[str, str]
    log_path: str
    proc: Optional[subprocess.Popen] = None
    _log_file = None

    def start(self) -> None:
        os.makedirs(os.path.dirname(self.log_path) or ".", exist_ok=True)
        self._log_file = open(self.log_path, "ab", buffering=0)
        full_env = {**os.environ, **self.env}
        self.proc = subprocess.Popen(
            self.entrypoint, env=full_env, stdout=self._log_file,
            stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    @property
    def returncode(self) -> Optional[int]:
        return None if self.proc is None else self.proc.poll()

    def terminate(self, grace: float = 10.0) -> None:
        """SIGTERM (checkpoint window for preemption-aware loops), then
        SIGKILL the whole process group."""
        if self.proc is None or self.proc.poll() is not None:
            self._close_log()
            return
        try:
            os.killpg(self.proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        deadline = time.monotonic() + grace
        while time.monotonic() < deadline and self.proc.poll() is None:
            time.sleep(0.05)
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._close_log()

    def _close_log(self):
        if self._log_file is not None:
            self._log_file.close()
            self._log_file = None


@dataclasses.dataclass
class Pod:
    """Per-node set of containers (reference: job/pod.py)."""

    containers: List[Container] = dataclasses.field(default_factory=list)

    def deploy(self) -> None:
        for c in self.containers:
            c.start()

    def alive(self) -> bool:
        return any(c.alive() for c in self.containers)

    def failed(self) -> bool:
        return any(c.returncode not in (None, 0) for c in self.containers)

    def done(self) -> bool:
        return all(c.returncode == 0 for c in self.containers)

    def stop(self, grace: float = 10.0) -> None:
        for c in self.containers:
            c.terminate(grace)

    def join(self, poll: float = 0.2) -> int:
        """Wait until all containers exit; first nonzero code, else 0."""
        while self.alive():
            time.sleep(poll)
        codes = [c.returncode or 0 for c in self.containers]
        return next((c for c in codes if c), 0)


@dataclasses.dataclass
class Job:
    job_id: str
    nnodes: int
    nproc_per_node: int

    @property
    def world_size(self) -> int:
        return self.nnodes * self.nproc_per_node


def build_container(ctx, global_rank: int, local_rank: int, world_size: int,
                    coordinator: str, endpoints: List[str]) -> Container:
    """Inject the env protocol (reference PADDLE_* names kept for script
    portability; PDTPU_* consumed by paddle_tpu.distributed)."""
    env = {
        # reference protocol (scripts ported from paddle read these)
        "PADDLE_TRAINER_ID": str(global_rank),
        "PADDLE_TRAINERS_NUM": str(world_size),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_CURRENT_ENDPOINT": endpoints[global_rank],
        "PADDLE_MASTER": coordinator,
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_JOB_ID": ctx.job_id,
        # native protocol (paddle_tpu.distributed.init_parallel_env)
        "PDTPU_COORDINATOR": coordinator,
        "PDTPU_PROCESS_ID": str(global_rank),
        "PDTPU_NUM_PROCESSES": str(world_size),
        "PDTPU_LOCAL_RANK": str(local_rank),
    }
    if ctx.devices is not None:
        # the variable libtpu reads when the child first touches jax (a
        # TPU process ignores CUDA_VISIBLE_DEVICES); the launcher itself
        # never initialises a backend, so the chips are the child's
        env["TPU_VISIBLE_CHIPS"] = ctx.devices
    log_path = os.path.join(ctx.log_dir,
                            f"workerlog.{global_rank}")
    entry = [sys.executable, "-u", ctx.script, *ctx.script_args]
    return Container(entrypoint=entry, env=env, log_path=log_path)
