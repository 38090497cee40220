"""EVA's attention kernel against its roofline: the k and v bytes of the
live pages of both kinds (exact window pages, chunk-summary pages), which
every step must read once, and the attention operations of the live span
tokens over the keys and summaries they see, over the summed device time
of the events below in the traced seconds.  The live pages are the
engine's own count (``kv_blocks_used``, pages of both kinds), polled every
50 ms while the trace runs; a request's live rows are the keys its next
query sees.  On a program without the kernel there is nothing to read."""

from benchmark import trace_reduce
from benchmark.work import counters, eva, transformer

PATTERNS = [r"eva_ragged_paged_attention"]
REGISTRY = ["serve.ragged_occupancy"]


def read(ctx):
    if not ctx["trace"]["devices"]:
        return None
    ops = ctx["trace"]["devices"][0]["ops"]
    evs = trace_reduce.leaf_ops(trace_reduce.matching(
        ops, PATTERNS, ctx["scopes"]))
    seconds = sum(e[2] for e in evs)
    runs = trace_reduce.module_runs(ctx["trace"], ctx["cell"]["step_program"])
    if seconds <= 0 or not runs or not ctx["counters"].get("kv_blocks_polls"):
        return None
    eng = ctx["engine"]
    live_rows, slots = transformer.live_context(ctx["counters"], eng)
    nbytes = len(runs) * live_rows * eva.row_bytes(ctx["config"],
                                                   ctx["layers"])
    occ = counters.histogram(ctx, "traced", REGISTRY[0]) or (0.0, 0)
    processed = occ[0] * eng["max_batch"] * eng["prefill_chunk"]
    flops = processed * eva.attention_flops_per_token(
        ctx["config"], ctx["layers"], live_rows / slots)
    least, bound = transformer.roofline_seconds(flops, nbytes, ctx["peaks"])
    ctx["notes"].append(f"eva_attention_roofline: bound by {bound}, "
                        f"{len(evs)} events, {seconds:.6f} s in "
                        f"{len(runs)} steps, {live_rows:.0f} live rows in "
                        f"{slots} slots")
    return 100.0 * least / seconds
