"""The whole step's share of the chip's bf16 peak: the benchmark's own
operations per token (6 x multiplied parameters + causal attention,
recomputation uncredited) times the tokens per second of the traced
steps, whole periods from one step program's start to the next on the
device's own clock, over the peak."""

from benchmark import trace_reduce
from benchmark.work import transformer


def read(ctx):
    per = trace_reduce.step_periods(ctx["trace"], ctx["cell"]["step_program"])
    if per is None or per["seconds"] <= 0:
        return None
    t = ctx["cell"]["traffic"]
    tokens_per_s = per["periods"] * t["batch"] * t["seq"] / per["seconds"]
    flops = transformer.train_flops_per_token(ctx["config"], ctx["layers"],
                                              t["seq"])
    return 100.0 * flops * tokens_per_s / ctx["peaks"]["bf16_flops_per_s"]
