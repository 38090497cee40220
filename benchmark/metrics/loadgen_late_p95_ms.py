"""How late the load generator ran: 95th percentile, over the window's
requests, of the time a request was sent minus the time it was due.  A
starved generator must not be read as a fast server."""


def read(ctx):
    return ctx["client"].get("loadgen_late_p95_ms")
