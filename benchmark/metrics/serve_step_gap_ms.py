"""The median idle gap of the device between two consecutive runs of the
step program in the traced seconds: the host's round trip per step."""

import statistics

from benchmark import trace_reduce


def read(ctx):
    runs = trace_reduce.module_runs(ctx["trace"], ctx["cell"]["step_program"])
    if len(runs) < 3:
        return None
    gaps = [max(0.0, b[1] - (a[1] + a[2])) for a, b in zip(runs, runs[1:])]
    ctx["notes"].append(f"serve_step_gap_ms: {len(runs)} steps, median step "
                        f"{1e3 * statistics.median(r[2] for r in runs):.3f} ms")
    return 1e3 * statistics.median(gaps)
