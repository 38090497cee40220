"""Bytes that the AdamW update must read and write per parameter (28),
over the summed device time of the events below within the traced whole
steps."""

from benchmark import trace_reduce
from benchmark.work import transformer

PATTERNS = [r"fused_adamw"]


def read(ctx):
    found = trace_reduce.kernel_time_in_steps(
        ctx["trace"], ctx["cell"]["step_program"], PATTERNS, ctx["scopes"])
    if found is None:
        return None
    seconds, events, n = found
    nbytes = n * transformer.adamw_bytes(ctx["counters"]["n_params"])
    least, bound = transformer.roofline_seconds(0.0, nbytes, ctx["peaks"])
    ctx["notes"].append(f"adamw_roofline: bound by {bound}, {events} "
                        f"events, {seconds:.6f} s in {n} steps")
    return 100.0 * least / seconds
