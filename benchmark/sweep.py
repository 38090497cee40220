#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip, in one process with one
set-up::

    python3 benchmark/sweep.py --workload mistral-7b.serve-chat \
        --rates 1,2,3,4,6,8 --seconds 30 [--seed 1]

For each rate the cell's own traffic runs open-loop for ``--seconds``
(after the cell's lead-in), then drains.  A line of JSON a rate: requests
sent and failed, the tails, the tokens per second delivered, and the
backlog (requests in flight) at the window's middle and at its end.  The
knee is the highest rate at which the backlog does not grow through the
window and no request fails; the cell's file then holds ``rate_rps`` =
0.8 x the knee as a number, and ``knee_rps`` beside it.  The benchmark's
own runs never call this file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def in_flight(records: list, t: float) -> int:
    n = 0
    for r in records:
        if r["sent_s"] is None or r["sent_s"] > t:
            continue
        ended = r["token_s"][-1] if (r["done"] and r["token_s"]) else None
        if ended is None or ended > t:
            n += 1
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from benchmark import run as brun
    from benchmark.runners import serve

    cell = brun.load_json("workloads", args.workload + ".json")
    config = brun.load_json("configs", cell["config"] + ".json")
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU - nothing was run")
    brun.configure_cache()
    ctx = {"name": args.workload, "cell": cell, "config": config,
           "seed": args.seed, "seconds": args.seconds, "trace": False,
           "t_start": time.perf_counter(),
           "device_report": lambda: brun.device_report(jax.devices(), 1)}
    prog = serve.Program(ctx)
    out = open(args.out, "a") if args.out else None
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            ctx["seed"] = args.seed + 1000 * i
            schedule = serve.make_schedule(ctx, rate_rps=rate)
            info = serve.drive(ctx, prog, schedule, args.seconds)
            m = serve.client_metrics(
                info["records"], schedule, args.seconds,
                1e3 * float(cell["traffic"]["grace_s"]))
            row = {"rate_rps": rate, **m,
                   "in_flight_mid": in_flight(info["records"],
                                              args.seconds / 2),
                   "in_flight_end": in_flight(info["records"], args.seconds),
                   "memory_peak_bytes": info["device"]["memory_peak_bytes"]}
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        prog.stop()
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
