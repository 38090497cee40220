"""Device milliseconds of the usual serving step under the region
``mlp``: the MLP kernel and, beside it, the compaction's gathers."""

from benchmark.work import regions


def read(ctx):
    return regions.step_median_ms(ctx, "mlp")
