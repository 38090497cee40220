"""How full the one compiled (max_batch, prefill_chunk) step ran: the
mean over the window's steps of the registry histogram
``serve.ragged_occupancy`` (live span tokens over the lanes).  The
registry exists only under ``observability.enable()``, which the traced
run turns on."""

from benchmark.work import counters

REGISTRY = ["serve.ragged_occupancy"]


def read(ctx):
    mean = counters.mean(ctx, "window", REGISTRY[0])
    return None if mean is None else 100.0 * mean
