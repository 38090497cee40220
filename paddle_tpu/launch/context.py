"""Launcher context: CLI args + environment + device detection.

Reference: python/paddle/distributed/launch/context/ (args parsing, Node
device detection) and the PADDLE_* env protocol set in
controllers/controller.py.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import socket
from typing import List, Optional


@dataclasses.dataclass
class Context:
    script: str = ""
    script_args: List[str] = dataclasses.field(default_factory=list)
    nnodes: int = 1                       # max (= target) node count
    nnodes_min: Optional[int] = None      # elastic: accept >= this many
    nproc_per_node: int = 1
    master: Optional[str] = None          # host:port of rendezvous store
    rank: int = -1                        # node rank; -1 = assigned by master
    job_id: str = "default"
    log_dir: str = "log"
    elastic_level: int = 0                # 0=off, 1=restart on failure
    elastic_timeout: float = 30.0
    max_restarts: int = 3
    devices: Optional[str] = None         # chip ids the children may see
    host: str = dataclasses.field(default_factory=socket.gethostname)

    @property
    def max_world_size(self) -> int:
        """Upper bound from the CLI; the ACTUAL world size after an elastic
        settle is len(frozen membership) * nproc_per_node (controller)."""
        return self.nnodes * self.nproc_per_node

    @property
    def min_nodes(self) -> int:
        return self.nnodes if self.nnodes_min is None else self.nnodes_min


def _parse_nnodes(value) -> tuple:
    """``--nnodes 2`` → (2, 2); ``--nnodes 1:4`` → (1, 4) (reference elastic
    range syntax: python/paddle/distributed/launch/context/args_envs.py)."""
    s = str(value)
    if ":" in s:
        lo, hi = s.split(":", 1)
        lo, hi = int(lo), int(hi)
        if not 1 <= lo <= hi:
            raise ValueError(f"bad --nnodes range {s!r}")
        return lo, hi
    n = int(s)
    return n, n


def parse_args(argv: Optional[List[str]] = None) -> Context:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.launch",
        description="paddle_tpu distributed launcher (fleetrun parity)")
    p.add_argument("--nnodes", type=str,
                   default=os.environ.get("PADDLE_NNODES", "1"),
                   help="node count, or MIN:MAX for an elastic range")
    p.add_argument("--nproc_per_node", type=int, default=None,
                   help="processes per node; default 1 (a TPU host drives "
                        "all local chips from one process)")
    p.add_argument("--master", default=os.environ.get("PADDLE_MASTER"),
                   help="host:port of the rendezvous store (node rank 0)")
    p.add_argument("--rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", -1)))
    p.add_argument("--job_id", default=os.environ.get("PADDLE_JOB_ID",
                                                      "default"))
    p.add_argument("--log_dir", default="log")
    p.add_argument("--elastic_level", type=int,
                   default=int(os.environ.get("PADDLE_ELASTIC_LEVEL", 0)))
    p.add_argument("--elastic_timeout", type=float,
                   default=float(os.environ.get("PADDLE_ELASTIC_TIMEOUT", 30)))
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--devices", default=None)
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    a = p.parse_args(argv)
    nmin, nmax = _parse_nnodes(a.nnodes)
    return Context(
        script=a.script, script_args=a.script_args, nnodes=nmax,
        nnodes_min=nmin,
        nproc_per_node=a.nproc_per_node or 1, master=a.master, rank=a.rank,
        job_id=a.job_id, log_dir=a.log_dir, elastic_level=a.elastic_level,
        elastic_timeout=a.elastic_timeout, max_restarts=a.max_restarts,
        devices=a.devices)
