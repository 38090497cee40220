"""The whole step's share of the chip's bf16 peak over the traced
seconds: the forward operations the algorithm needs for the prompt and
output tokens processed there (layers' matrices per processed token, the
head per emitted token, attention over the mean context), over the
traced window and the peak.  Padded lanes earn nothing."""

from benchmark import trace_reduce
from benchmark.work import counters, transformer

REGISTRY = ["serve.ragged_occupancy"]


def read(ctx):
    occ = counters.histogram(ctx, "traced", REGISTRY[0])
    bw = trace_reduce.busy_and_window(ctx["trace"])
    if occ is None or bw["window_s"] <= 0:
        return None
    eng = ctx["engine"]
    lanes = eng["max_batch"] * eng["prefill_chunk"]
    processed = occ[0] * lanes
    live_tokens, slots = transformer.live_context(ctx["counters"], eng)
    flops = transformer.serve_flops(
        ctx["config"], ctx["layers"], processed,
        ctx["counters"].get("traced_emitted", 0), live_tokens / slots)
    return 100.0 * flops / (bw["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
