"""The whole Qwen3-Next step's share of the chip's bf16 peak: the
benchmark's own operations per token (6 x the parameters a token is
multiplied with, the routed experts at the share held here, + the full
layers' causal attention + the delta rule's recurrence, recomputation
uncredited) times the tokens per second of the traced steps, whole
periods from one step program's start to the next on the device's own
clock, over the peak."""

from benchmark import trace_reduce
from benchmark.work import qwen3_next


def read(ctx):
    per = trace_reduce.step_periods(ctx["trace"], ctx["cell"]["step_program"])
    if per is None or per["seconds"] <= 0:
        return None
    t = ctx["cell"]["traffic"]
    tokens_per_s = per["periods"] * t["batch"] * t["seq"] / per["seconds"]
    flops = qwen3_next.train_flops_per_token(ctx["config"], ctx["layers"],
                                             t["seq"])
    return 100.0 * flops * tokens_per_s / ctx["peaks"]["bf16_flops_per_s"]
