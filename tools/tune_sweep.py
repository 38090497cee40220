#!/usr/bin/env python
"""Re-sweep a previously-rejected tuning knob after the matmul-rope step
change (BENCH.md §attribution): bs8 + chunked CE was rejected at the
r2/r3 cost structure; the layout-traffic profile changed, so re-measure.

Usage: python tools/tune_sweep.py [--steps 15] [--windows 2]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=15)
    ap.add_argument("--windows", type=int, default=2)
    ap.add_argument("--preset", default="llama-350m")
    args = ap.parse_args()
    import bench

    cases = [
        ("bs4", dict(batch_size=4, loss_chunks=1)),
        ("bs8+ce8", dict(batch_size=8, loss_chunks=8)),
    ]
    out = {}
    print("| case | mfu | ms/step | tok/s/chip |")
    print("|---|---|---|---|")
    for name, kw in cases:
        try:
            mfu, stats = bench.measure(args.preset, kw["batch_size"], 2048,
                                       args.steps, args.windows,
                                       loss_chunks=kw["loss_chunks"])
            print(f"| {name} | {mfu:.4f} | {stats['ms_per_step']} "
                  f"| {stats['tokens_per_sec_per_chip']} |", flush=True)
            out[name] = {"mfu": round(mfu, 4),
                         "ms_per_step": stats["ms_per_step"]}
        except Exception as e:  # keep sweeping past a config that OOMs
            print(f"| {name} | ERROR {type(e).__name__} | | |", flush=True)
            out[name] = {"error": str(e)[:200]}
    print()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
