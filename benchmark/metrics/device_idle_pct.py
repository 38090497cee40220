"""Share of the traced window, first device operation to last, in which
no operation ran on the chip."""

from benchmark import trace_reduce


def read(ctx):
    bw = trace_reduce.busy_and_window(ctx["trace"])
    if bw["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - bw["busy_s"] / bw["window_s"])
