"""The held experts' grouped products against their roofline: three
matrices forward, data and weight gradients at the expected rows a layer
(tokens x experts per token x the share held), and the bytes of the held
experts' leaves read and their gradients written, over the summed device
time of the leaf events of the traced whole steps under the scope
``moe_experts``: the gather, the products, the scatter and the backward
pass's recomputation of them, the last uncredited.  XLA's own grouped
kernel carries no scope path (its ``op_name`` is ``ragged-dot-...``), so
it is matched by that name.  On a program without either there is nothing
to read."""

from benchmark import trace_reduce
from benchmark.work import qwen3_next, transformer

PATTERNS = [r"moe_experts", r"^ragged-dot"]


def read(ctx):
    found = trace_reduce.kernel_time_in_steps(
        ctx["trace"], ctx["cell"]["step_program"], PATTERNS, ctx["scopes"])
    if found is None:
        return None
    seconds, events, n = found
    t = ctx["cell"]["traffic"]
    flops = n * qwen3_next.experts_flops(ctx["config"], ctx["layers"],
                                         t["batch"] * t["seq"])
    nbytes = n * qwen3_next.experts_bytes(ctx["config"], ctx["layers"])
    least, bound = transformer.roofline_seconds(flops, nbytes, ctx["peaks"])
    ctx["notes"].append(f"moe_experts_roofline: bound by {bound}, {events} "
                        f"events, {seconds:.6f} s in {n} steps, "
                        f"{least:.6f} s at the roofline")
    return 100.0 * least / seconds
