"""Host time of the serving loop by phase.

``ServingServer._loop`` tiles one iteration with leaf spans on its own
thread (``pdtpu.serve.loop.wait``, ``serve.pump``, ``serve.step.{admit,
draft,plan,dispatch,sync,emit,account}``, ``serve.stream.route``), which
the profiler's trace holds on the device events' clock.  A phase's host
milliseconds per step are its self time on that thread between the first
traced run of the step program and the last one's start, over the periods
between them: the thread is the one that owns the device's idle gaps
(``trace_reduce.gap_owners``).  ``sync`` and ``loop.wait`` are waiting,
for the device and for work, and belong to no reader.

Self time is on the host's clock alone.  What a phase owns of the idle
gaps is not: the profiler sets the device's clock against the host's anew
in every session, and the two have read a millisecond apart (a step
program that starts before the host calls it).  That moves seconds
between ``dispatch`` and ``sync`` in ``idle_gaps``, so the run's log also
says where a step's launch and its end fell in those two spans.
"""

from __future__ import annotations

import bisect
import statistics

from benchmark import trace_reduce

PREFIX = "pdtpu."
DISPATCH, SYNC = PREFIX + "serve.step.dispatch", PREFIX + "serve.step.sync"


def launch_and_end(spans, runs):
    """(median ms from a dispatch span's start to the next start of the
    step program, median ms from the program's end to the end of the sync
    span it ended in), each None where nothing pairs up."""
    starts = [r[1] for r in runs]
    ends = [r[1] + r[2] for r in runs]
    lead, tail = [], []
    for name, start, dur in (e[:3] for e in spans):
        if name == DISPATCH:
            i = bisect.bisect_left(starts, start)
            if i < len(starts):
                lead.append(1e3 * (starts[i] - start))
        elif name == SYNC:
            i = bisect.bisect_right(ends, start + dur) - 1
            if i >= 0 and ends[i] > start:
                tail.append(1e3 * (start + dur - ends[i]))
    return tuple(statistics.median(x) if x else None for x in (lead, tail))


def phase_table(ctx: dict):
    """{"steps": periods, "ms": {span name: self ms per step}, "gap_ms":
    {owner: ms per step inside the device's idle gaps}} or None where no
    two steps ran or the loop's thread wrote no span.  Reckoned once a
    run; the table goes to the run's log."""
    if "phase_table" in ctx:
        return ctx["phase_table"]
    table = None
    per = trace_reduce.step_periods(ctx["trace"], ctx["cell"]["step_program"])
    if per is not None and per["seconds"] > 0:
        t0, t1, n = per["t0"], per["t1"], per["periods"]
        ops = trace_reduce.clip_events(ctx["trace"]["devices"][0]["ops"],
                                       t0, t1)
        thread, rows = trace_reduce.gap_owners(
            trace_reduce.idle_gaps(ops, t0, t1), ctx["trace"]["host"])
        spans = trace_reduce.by_thread(ctx["trace"]["host"]).get(thread)
        if spans:
            ms, gap_ms = {}, {}
            for a, b, name in trace_reduce.self_segments(spans):
                lo, hi = max(a, t0), min(b, t1)
                if hi > lo:
                    ms[name] = ms.get(name, 0.0) + 1e3 * (hi - lo) / n
            for _, row in rows:
                for name, sec in row.items():
                    gap_ms[name] = gap_ms.get(name, 0.0) + 1e3 * sec / n
            table = {"steps": n, "ms": ms, "gap_ms": gap_ms}
            ctx["notes"].append(
                f"host ms per step by phase over {n} periods of "
                f"{1e3 * per['seconds'] / n:.3f} ms (self time / of it "
                f"inside the device's idle gaps): " + ", ".join(
                    f"{k} {v:.3f} / {gap_ms.get(k, 0.0):.3f}"
                    for k, v in sorted(ms.items(), key=lambda kv: -kv[1]))
                + f"; unattributed gap "
                f"{gap_ms.get(trace_reduce.UNATTRIBUTED, 0.0):.3f}")
            lead, tail = (x if x is None else round(x, 3)
                          for x in launch_and_end(spans, per["runs"]))
            ctx["notes"].append(
                f"by the trace's two clocks the step program starts "
                f"{lead} ms into serve.step.dispatch and ends {tail} ms "
                f"before serve.step.sync does (medians)")
    ctx["phase_table"] = table
    return table


def ms_per_step(ctx: dict, phases):
    """Summed self milliseconds per step of the named phases (span names
    without the ``pdtpu.`` prefix), or None where none of them was seen."""
    table = phase_table(ctx)
    if table is None:
        return None
    total = sum(table["ms"].get(PREFIX + p, 0.0) for p in phases)
    return total if total > 0 else None
