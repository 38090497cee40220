"""The gated delta rule against its roofline: the recurrence's operations
and the bytes of q, k, v, g, beta read and o written, forward and
backward, of the gated-delta layers, over the summed device time of the
leaf events of the traced whole steps whose scope path holds
``gated_delta_rule`` (forward, backward and the recomputation, the last
uncredited).  On a program without the scope there is nothing to read."""

from benchmark import trace_reduce
from benchmark.work import qwen3_next, transformer

PATTERNS = [r"gated_delta_rule"]


def read(ctx):
    found = trace_reduce.kernel_time_in_steps(
        ctx["trace"], ctx["cell"]["step_program"], PATTERNS, ctx["scopes"])
    if found is None:
        return None
    seconds, events, n = found
    t = ctx["cell"]["traffic"]
    delta, _ = qwen3_next.layer_kinds(ctx["config"], ctx["layers"])
    tokens = n * t["batch"] * t["seq"] * delta
    flops = 3.0 * tokens * qwen3_next.delta_rule_flops_per_token(
        ctx["config"])
    nbytes = tokens * qwen3_next.delta_rule_bytes_per_token(ctx["config"])
    least, bound = transformer.roofline_seconds(flops, nbytes, ctx["peaks"])
    ctx["notes"].append(f"gated_delta_roofline: bound by {bound}, {events} "
                        f"events, {seconds:.6f} s in {n} steps, "
                        f"{least:.6f} s at the roofline")
    return 100.0 * least / seconds
