"""The keys and values of the live pages, which every step must read
once, and the attention operations of the live span tokens, over the
summed device time of the events below in the traced seconds.  The live
pages are the engine's own count (``kv_blocks_used``), polled every 50 ms
while the trace runs."""

from benchmark import trace_reduce
from benchmark.work import counters, transformer

PATTERNS = [r"ragged_paged_attention"]
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
    live_tokens, slots = transformer.live_context(ctx["counters"], eng)
    nbytes = len(runs) * live_tokens * transformer.kv_bytes_per_token(
        ctx["config"], ctx["layers"])
    occ = counters.histogram(ctx, "traced", REGISTRY[0]) or (0.0, 0)
    processed = occ[0] * eng["max_batch"] * eng["prefill_chunk"]
    h = ctx["config"]["num_attention_heads"] * ctx["config"]["head_dim"]
    context = live_tokens / slots
    flops = 4.0 * h * context * ctx["layers"] * processed
    least, bound = transformer.roofline_seconds(flops, nbytes, ctx["peaks"])
    ctx["notes"].append(f"ragged_attention_roofline: bound by {bound}, "
                        f"{len(evs)} events, {seconds:.6f} s in "
                        f"{len(runs)} steps, {live_tokens:.0f} live tokens")
    return 100.0 * least / seconds
