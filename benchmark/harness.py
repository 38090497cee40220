"""What every runner needs from the harness: the profiler around a part
of the window, the per-layer readers, the checks against their limits and
the result's line."""

from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile

from benchmark import trace_reduce


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


class Tracer:
    """``start()`` ... ``stop()`` around some seconds of the window; the
    trace goes to a directory under TMPDIR and is deleted once read."""

    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def read(self) -> dict:
        try:
            return trace_reduce.read_xplane(
                trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's own trace (``bench.<name>``), so that
    an idle gap of the device gets an owner; the program's own spans are
    there as ``pdtpu.<name>`` and ``trace_reduce`` reads both."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def release() -> None:
    """Free what the program left on the device before the reference."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def judge(numbers: dict, limits: dict):
    """``numbers``: name -> (value, where).  Every number has a limit of
    its own in the cell's file; one without is an error, not a pass.  A
    limit of ``null`` there says that the number has no upper reading
    (PERF.md names it with its readings): it is read, logged and not
    compared.  Returns (correct, checks)."""
    checks, ok = {}, True
    for name, (value, at) in numbers.items():
        if name not in limits:
            raise KeyError(f"the cell's file gives no limit for {name!r}")
        limit = limits[name]
        if limit is None:
            log(f"not compared: {name} = {value!r} ({at})")
            continue
        good = value == value and value <= limit      # NaN fails
        ok = ok and good
        checks[name] = {"value": value, "limit": limit, "at": str(at)}
    return ok, checks


def registry_names(ctx: dict) -> list:
    """The entries of the program's metrics registry that this cell's
    per-layer readers name (``REGISTRY = [...]`` in a metric's file): what
    a runner snapshots, since the registry is gone before a reader runs."""
    names = set()
    for name in ctx["per_layer"]:
        names.update(getattr(ctx["load_metric"](name), "REGISTRY", ()))
    return sorted(names)


def per_layer_metrics(ctx: dict, rctx: dict) -> dict:
    """Run this cell's readers.  One that finds nothing to read returns
    None and its metric is left out of the line."""
    out = {}
    rctx.setdefault("notes", [])
    for name, unit in ctx["per_layer"].items():
        value = ctx["load_metric"](name).read(rctx)
        if value is not None:
            out[name] = {"value": value, "unit": unit}
    for note in rctx["notes"]:
        log(note)
    return out


def result_line(ctx: dict, *, correct: bool, attempted: int, failed: int,
                end_to_end: dict, checks: dict, device: dict,
                rctx: dict = None) -> dict:
    """The result as the contract words it.  Untraced: the cell's
    end-to-end metrics.  Traced: its per-layer metrics, the device's busy
    seconds and the breakdown."""
    res = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    if ctx["trace"]:
        res["metrics"] = per_layer_metrics(ctx, rctx)
        bw = trace_reduce.busy_and_window(rctx["trace"])
        device = dict(device, busy_s=bw["busy_s"], window_s=bw["window_s"])
        res["device"] = device
        res["breakdown"] = trace_reduce.breakdown(rctx["trace"])
        log("device operations by time, seconds in the traced window:",
            trace_reduce.top_ops(rctx["trace"], 40))
        log("the longest idle gaps of the device, start on the trace's "
            "clock, milliseconds, owners:",
            trace_reduce.longest_idle_gaps(rctx["trace"]))
    else:
        res["metrics"] = {n: {"value": end_to_end[n], "unit": u}
                          for n, u in ctx["end_to_end"].items()}
        res["device"] = device
    res["checks"] = checks
    return res


@contextlib.contextmanager
def sentinel():
    """The program's RecompileSentinel, installed for the window."""
    from paddle_tpu.observability.recompile import RecompileSentinel

    s = RecompileSentinel()
    s.install()
    try:
        yield s
    finally:
        s.uninstall()
