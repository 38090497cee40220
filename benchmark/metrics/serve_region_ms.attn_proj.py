"""Device milliseconds of the usual serving step under the region
``attn_proj``: the q, k, v and output projections, RoPE and the residual add."""

from benchmark.work import regions


def read(ctx):
    return regions.step_median_ms(ctx, "attn_proj")
