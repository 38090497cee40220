"""The whole EVA step's share of the chip's bf16 peak over the traced
seconds: the forward operations the algorithm needs for the prompt and
output bytes processed there (layers' matrices per processed byte, head
0 per emitted byte, attention over the keys and summaries EVA sees, the
summariser's products), over the traced window and the peak.  Padded
lanes and the seven unread prediction heads earn nothing."""

from benchmark import trace_reduce
from benchmark.work import counters, eva, transformer

REGISTRY = ["serve.ragged_occupancy"]


def read(ctx):
    occ = counters.histogram(ctx, "traced", REGISTRY[0])
    bw = trace_reduce.busy_and_window(ctx["trace"])
    if occ is None or bw["window_s"] <= 0:
        return None
    eng = ctx["engine"]
    processed = occ[0] * eng["max_batch"] * eng["prefill_chunk"]
    live_rows, slots = transformer.live_context(ctx["counters"], eng)
    flops = eva.serve_flops(ctx["config"], ctx["layers"], processed,
                            ctx["counters"].get("traced_emitted", 0),
                            live_rows / slots)
    return 100.0 * flops / (bw["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
