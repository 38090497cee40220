"""What the runner kept of the program's metrics registry: the entries
the cell's readers name (``REGISTRY`` in a metric's file), as deltas over
the window (``"window"``) and over the traced seconds (``"traced"``):
``{"sum", "count"}`` of a histogram, ``{"value"}`` of a counter."""

from __future__ import annotations


def histogram(ctx: dict, over: str, name: str):
    """(sum, count) of the histogram's observations in the window or the
    traced seconds, or None where it observed nothing there."""
    entry = (ctx["counters"].get(over) or {}).get(name)
    if not entry or not entry.get("count"):
        return None
    return entry["sum"], entry["count"]


def mean(ctx: dict, over: str, name: str):
    got = histogram(ctx, over, name)
    return None if got is None else got[0] / got[1]
