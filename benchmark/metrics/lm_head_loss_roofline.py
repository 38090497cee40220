"""The output head's forward and backward operations from the shapes, over
the peak, against the summed device time of the leaf events of the traced
whole steps whose scope path lies in the region ``lm_head_loss``: the head
matmuls and the softmax cross-entropy over the vocabulary."""

from benchmark.work import regions


def read(ctx):
    found = regions.region_seconds(ctx, "lm_head_loss")
    if found is None:
        return None
    seconds, events, n = found
    t = ctx["cell"]["traffic"]
    flops = n * regions.lm_head_train_flops(ctx["config"],
                                            t["batch"] * t["seq"])
    least = flops / ctx["peaks"]["bf16_flops_per_s"]
    ctx["notes"].append(f"lm_head_loss_roofline: {events} events, "
                        f"{seconds:.6f} s in {n} steps, {least:.6f} s at peak")
    return 100.0 * least / seconds
