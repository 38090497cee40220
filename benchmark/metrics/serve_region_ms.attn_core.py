"""Device milliseconds of the usual serving step under the region
``attn_core``: the ragged attention kernel, a cache kind's summariser and
the copies around them."""

from benchmark.work import regions


def read(ctx):
    return regions.step_median_ms(ctx, "attn_core")
