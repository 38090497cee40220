"""Mean wait of a request between its submission and its admission to a
slot (and between a preemption and its re-admission), over the episodes
that ended in the window: ``RequestTracer``'s ``serve.queue_ms``."""

from benchmark.work import counters

REGISTRY = ["serve.queue_ms"]


def read(ctx):
    return counters.mean(ctx, "window", REGISTRY[0])
