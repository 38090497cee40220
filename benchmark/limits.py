#!/usr/bin/env python3
"""The readings a cell's limits are set from, taken on the chip at the
cell's own size, in one process::

    python3 benchmark/limits.py --workload <cell> --seeds 1,2,3,... \
        --control-seeds 1,2,3 [--out chiprun_out/limits.jsonl]

For every seed: the program's numbers against the plain reference (the
lower readings).  For every control seed also the control (the reference
in the program's place, computed in float8) and each planted fault,
against the same reference (the upper readings).  Every row is judged as
a run judges it, by the limits in the cell's file: the exit code is 0
only if ``correct`` came out true in every row of the program and false
in every row of the control and of a fault.  Training cells need no
measured window for this: the numbers are of the first three steps.  A
serving cell sets up once (its weights from the first seed) and runs one
short window at the cell's own load for every seed.
The benchmark's own runs never call this file.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from benchmark import run as brun

    cell = brun.load_json("workloads", args.workload + ".json")
    config = brun.load_json("configs", cell["config"] + ".json")
    import jax

    brun.configure_cache()
    runner = importlib.import_module("benchmark.runners." + cell["runner"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    ctx = {"name": args.workload, "cell": cell, "config": config,
           "trace": False, "t_start": time.perf_counter(),
           "device_report": lambda: brun.device_report(jax.devices(),
                                                       int(cell["chips"]))}
    wrong = []
    for row in runner.limit_readings(ctx, seeds, control):
        line = json.dumps({"workload": args.workload, **row})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        if row["correct"] != (row["side"] == "program"):
            wrong.append((row["seed"], row["side"], row["correct"]))
    if out:
        out.close()
    print("limits: rows judged against their side:",
          wrong or "all as they must be", file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
