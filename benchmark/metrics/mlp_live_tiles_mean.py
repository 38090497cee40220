"""Mean number of 128-token tiles the MLP kernel multiplied in a step
of the window (``ceil(live tokens / 128)``; 1 = the weights' one crossing
of HBM and no more): the histogram ``serve.mlp_live_tiles``."""

from benchmark.work import counters

REGISTRY = ["serve.mlp_live_tiles"]


def read(ctx):
    return counters.mean(ctx, "window", REGISTRY[0])
