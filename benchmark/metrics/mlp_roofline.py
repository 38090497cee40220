"""The feed-forward block's forward and backward operations from the
shapes, over the peak, against the summed device time of the leaf events
of the traced whole steps whose scope path lies in the region ``mlp``:
forward, backward and any recomputation together, the last uncredited."""

from benchmark.work import regions


def read(ctx):
    found = regions.region_seconds(ctx, "mlp")
    if found is None:
        return None
    seconds, events, n = found
    t = ctx["cell"]["traffic"]
    flops = n * regions.mlp_train_flops(ctx["config"], ctx["layers"],
                                        t["batch"] * t["seq"])
    least = flops / ctx["peaks"]["bf16_flops_per_s"]
    ctx["notes"].append(f"mlp_roofline: {events} events, {seconds:.6f} s "
                        f"in {n} steps, {least:.6f} s at peak")
    return 100.0 * least / seconds
