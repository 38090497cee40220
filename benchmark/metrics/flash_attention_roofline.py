"""Causal attention's forward and backward operations and bytes from the
shapes, over the summed device time of the events below, within the
traced whole steps.  The events are today's only handle on "the
attention core": the Pallas kernels' names in the HLO scope path."""

from benchmark import trace_reduce
from benchmark.work import transformer

PATTERNS = [r"flash_attention_(fwd|bwd)"]


def read(ctx):
    found = trace_reduce.kernel_time_in_steps(
        ctx["trace"], ctx["cell"]["step_program"], PATTERNS, ctx["scopes"])
    if found is None:
        return None
    seconds, events, n = found
    t = ctx["cell"]["traffic"]
    flops = n * transformer.causal_attention_flops(
        ctx["config"], ctx["layers"], t["batch"], t["seq"], True)
    nbytes = n * transformer.causal_attention_bytes(
        ctx["config"], ctx["layers"], t["batch"], t["seq"], True)
    least, bound = transformer.roofline_seconds(flops, nbytes, ctx["peaks"])
    ctx["notes"].append(f"flash_attention_roofline: bound by {bound}, "
                        f"{events} events, {seconds:.6f} s in {n} steps")
    return 100.0 * least / seconds
