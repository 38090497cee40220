"""Mean number of steps from a request's first prefill chunk to its
first token, over the requests whose first token fell in the window: the
histogram ``serve.prefill_steps``."""

from benchmark.work import counters

REGISTRY = ["serve.prefill_steps"]


def read(ctx):
    return counters.mean(ctx, "window", REGISTRY[0])
