"""From the profiler's trace to numbers.  The trace is read with
``jax.profiler.ProfileData`` and nothing else; everything after
``read_xplane`` works on plain lists, so the arithmetic is pinned by a
hand-built event list in ``benchmark/tests``.

A trace, as this file holds it::

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_s, dur_s], ...],       # line "XLA Ops"
                  "modules": [[name, start_s, dur_s], ...]}], # "XLA Modules"
     "host": [[name, start_s, dur_s, thread], ...]}
     # the harness's ``bench.*`` spans and the program's ``pdtpu.*`` spans,
     # each with the host line it was on (a hand-built list may leave the
     # thread out: its spans are then on one thread)

Times are seconds on the trace's one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "pdtpu.")
UNATTRIBUTED = "unattributed"


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def short_name(name: str) -> str:
    """The device trace names an operation by its whole HLO text
    (``%fusion.3 = bf16[...] fusion(...)``): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": [], "host": [], "planes": []}
    for plane in data.planes:
        lines = list(plane.lines)
        out["planes"].append([plane.name, [ln.name for ln in lines]])
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for ln in lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(ln.name)
                if key is None:
                    continue
                for ev in ln.events:
                    dev[key].append([short_name(ev.name), ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9])
            dev["ops"].sort(key=lambda e: e[1])
            dev["modules"].sort(key=lambda e: e[1])
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(lines):
                # every Python thread's line is named "python": the
                # line's place in its plane tells them apart
                thread = f"{plane.name}/{i}:{ln.name}"
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        out["host"].append([ev.name, ev.start_ns * 1e-9,
                                            ev.duration_ns * 1e-9, thread])
    out["host"].sort(key=lambda e: e[1])
    return out


def union_seconds(events) -> float:
    """Seconds covered by at least one of the events (they may nest or
    overlap: a fusion inside a while loop's body, two cores' lines)."""
    total, end = 0.0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def span_of(events):
    """(first start, last end) of the events."""
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def clip_events(events, t0: float, t1: float) -> list:
    """The events cut to ``[t0, t1]``."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def busy_and_window(trace: dict, window=None) -> dict:
    """Busy seconds (union of the device's operations) and the window's
    length, averaged over the devices that ran anything.  The window is
    ``window`` = (t0, t1), or else the first operation's start to the last
    one's end."""
    busy, wins = [], []
    for dev in trace["devices"]:
        ops = dev["ops"]
        if not ops:
            continue
        t0, t1 = window if window is not None else span_of(ops)
        busy.append(union_seconds(clip_events(ops, t0, t1)))
        wins.append(t1 - t0)
    if not busy:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0}
    return {"busy_s": sum(busy) / len(busy), "window_s": sum(wins) / len(wins),
            "devices": len(busy)}


def matching(events, patterns, scopes: dict = None) -> list:
    """Events whose name, or whose HLO scope path (``scopes``: instruction
    name -> ``op_name``), matches one of the regular expressions."""
    regs = [re.compile(p) for p in patterns]
    out = []
    for ev in events:
        texts = [ev[0]]
        if scopes:
            scope = scopes.get(ev[0]) or scopes.get(ev[0].lstrip("%"))
            if scope:
                texts.append(scope)
        if any(r.search(t) for r in regs for t in texts):
            out.append(ev)
    return out


def leaf_ops(events) -> list:
    """The events that contain no other event: what really occupied the
    device (a ``while`` or a ``call`` spans its body's operations)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, ev in enumerate(evs):
        stop = ev[1] + ev[2]
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < stop and nxt[1] + nxt[2] <= stop \
                and (nxt[1] > ev[1] or nxt[2] < ev[2]):
            continue
        out.append(ev)
    return out


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time on
    the first device, containers left out."""
    if not trace["devices"]:
        return []
    sums = {}
    for name, _, dur in leaf_ops(trace["devices"][0]["ops"]):
        sums[name] = sums.get(name, 0.0) + dur
    return [[k, v] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, t0: float = None, t1: float = None) -> list:
    """[(start, end)] of the stretches inside ``[t0, t1]`` in which no
    operation ran."""
    if not events:
        return []
    evs = sorted(events, key=lambda e: e[1])
    lo, hi = span_of(evs)
    t0 = lo if t0 is None else t0
    t1 = hi if t1 is None else t1
    gaps, end = [], t0
    for _, start, dur in evs:
        if start > end:
            gaps.append((end, min(start, t1)))
        end = max(end, start + dur)
        if end >= t1:
            break
    if end < t1:
        gaps.append((end, t1))
    return [(a, b) for a, b in gaps if b > a]


def self_segments(spans) -> list:
    """One thread's spans as disjoint ``[(start, end, name)]`` in time
    order: every instant goes to the innermost span open at it, so a
    span's seconds here are its self time, its duration less what its
    children cover."""
    out, stack, cursor = [], [], None        # stack: [name, end]

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for name, start, dur in sorted((e[:3] for e in spans),
                                   key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][0]))
        cursor = start if cursor is None or not stack else max(cursor, start)
        stack.append([name, start + dur])
    close_until(float("inf"))
    return out


def by_thread(host_spans) -> dict:
    """thread -> its spans."""
    out = {}
    for ev in host_spans:
        out.setdefault(ev[3] if len(ev) > 3 else "", []).append(ev)
    return out


def _owned(gaps, segments) -> list:
    """For each gap, {name: seconds} of the segments' overlap with it;
    both in time order and disjoint, so one pass."""
    out, j = [], 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        row, k = {}, j
        while k < len(segments) and segments[k][0] < b:
            lo, hi = max(a, segments[k][0]), min(b, segments[k][1])
            if hi > lo:
                row[segments[k][2]] = row.get(segments[k][2], 0.0) + hi - lo
            k += 1
        out.append(row)
    return out


def gap_owners(gaps, host_spans):
    """(thread, rows): for each gap ``{name: seconds}`` by the self time
    of ONE thread's spans inside it, the rest of the gap under
    ``unattributed``, so that a gap's row sums to its length.  The
    thread is the one whose spans cover most of the gaps' seconds: the
    one that dispatches the step program (the serving loop, a training
    runner's main thread), whose phases tile its iteration.  Another
    thread's spans (a handler writing a stream) overlap those phases and
    would count seconds twice."""
    gaps = sorted(gaps)
    best, rows = None, [{} for _ in gaps]
    for thread, spans in sorted(by_thread(host_spans).items()):
        got = _owned(gaps, self_segments(spans))
        total = sum(v for row in got for v in row.values())
        if best is None or total > best[0]:
            best, rows = (total, thread), got
    for (a, b), row in zip(gaps, rows):
        rest = (b - a) - sum(row.values())
        if rest > 0:
            row[UNATTRIBUTED] = rest
    return (best[1] if best else None), list(zip(gaps, rows))


def attribute_gaps(gaps, host_spans, n: int = 10) -> list:
    """[[name, seconds]]: the gaps' seconds by owner (``gap_owners``),
    largest first, at most ``n`` rows: where there are more owners the
    smallest are folded into ``other``, so the rows still sum to the
    gaps' seconds."""
    owners = {}
    for _, row in gap_owners(gaps, host_spans)[1]:
        for name, sec in row.items():
            owners[name] = owners.get(name, 0.0) + sec
    rows = sorted(owners.items(), key=lambda kv: -kv[1])
    if len(rows) > n:
        rows = rows[:n - 1] + [("other", sum(v for _, v in rows[n - 1:]))]
    return [[k, v] for k, v in rows]


def module_runs(trace: dict, pattern: str) -> list:
    """The first device's runs of the programs whose name matches."""
    if not trace["devices"]:
        return []
    return matching(trace["devices"][0]["modules"], [pattern])


def kernel_time_in_steps(trace: dict, step_pattern: str, patterns,
                         scopes: dict = None):
    """(summed device seconds of the events matching ``patterns``, how
    many events, how many runs of the step program) within the first run's
    start to the last run's end.  None where there is nothing to read."""
    runs = module_runs(trace, step_pattern)
    if not runs:
        return None
    t0, t1 = span_of(runs)
    ops = clip_events(trace["devices"][0]["ops"], t0, t1)
    evs = leaf_ops(matching(ops, patterns, scopes))
    seconds = sum(e[2] for e in evs)
    if seconds <= 0:
        return None
    return seconds, len(evs), len(runs)


def breakdown(trace: dict) -> dict:
    if not trace["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    ops = trace["devices"][0]["ops"]
    return {"device_ops": top_ops(trace),
            "idle_gaps": attribute_gaps(idle_gaps(ops), trace["host"])}


def longest_idle_gaps(trace: dict, n: int = 5) -> list:
    """The first device's ``n`` longest idle gaps, each ``{"start_s",
    "ms", "owners"}`` with its owners' milliseconds, largest first: where
    a pause fell."""
    if not trace["devices"]:
        return []
    rows = gap_owners(idle_gaps(trace["devices"][0]["ops"]), trace["host"])[1]
    rows.sort(key=lambda r: r[0][0] - r[0][1])
    return [{"start_s": round(a, 6), "ms": round(1e3 * (b - a), 3),
             "owners": {k: round(1e3 * v, 3) for k, v in
                        sorted(row.items(), key=lambda kv: -kv[1])}}
            for (a, b), row in rows[:n]]


def step_periods(trace: dict, pattern: str):
    """The runs of the step program on the first device, and the seconds
    from the first run's start to the last run's start: ``n - 1`` whole
    periods, idle gaps between the steps included.  None under two runs."""
    runs = module_runs(trace, pattern)
    if len(runs) < 2:
        return None
    return {"runs": runs, "periods": len(runs) - 1,
            "seconds": runs[-1][1] - runs[0][1],
            "t0": runs[0][1], "t1": runs[-1][1]}
