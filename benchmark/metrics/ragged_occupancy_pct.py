"""How full the one compiled (max_batch, prefill_chunk) step ran: the
mean over the window's steps of the registry histogram
``serve.ragged_occupancy`` (live span tokens over the lanes).  The
registry exists only under ``observability.enable()``, which the traced
run turns on."""


def read(ctx):
    w = ctx["counters"].get("window") or {}
    if not w.get("occ_count"):
        return None
    return 100.0 * w["occ_sum"] / w["occ_count"]
