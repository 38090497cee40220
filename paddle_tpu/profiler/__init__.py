"""Profiler (``paddle.profiler`` parity over jax.profiler / XProf).

Reference (SURVEY.md §5.1): python/paddle/profiler/profiler.py — Profiler
with scheduler states (CLOSED/READY/RECORD), ``RecordEvent`` user scopes,
chrome-trace export, summary tables; C++ HostTracer + CUPTI device tracer.

TPU mapping: device-side timelines come from XLA via ``jax.profiler``
(xplane → TensorBoard/Perfetto — that's the CUPTI equivalent and needs no
code here beyond start/stop).  Host-side user scopes are recorded by
``RecordEvent`` and by ``observability.span`` alike: each is a host event
of every recording ``Profiler`` (exported as a chrome-trace JSON with a
summary table, preserving the reference's reporting surface) and a
``jax.profiler.TraceAnnotation`` named ``pdtpu.<name>`` on the host
timeline of whatever profiler session is live, on the device events' own
clock.  Neither writes into the device's lines: a region of a compiled
program is a ``jax.named_scope`` at TRACE time (observability/regions.py).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict
from enum import Enum
from typing import Callable, Iterable, List, Optional, Tuple

import jax

from ..observability import _state as _obs_state
from ..observability.spans import TRACE_PREFIX

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "active_profilers", "is_recording", "windowed_profiler"]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last step of a record window


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class _HostEvent:
    __slots__ = ("name", "start_ns", "end_ns", "tid")

    def __init__(self, name, start_ns, end_ns, tid):
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.tid = tid


_active_profilers: List["Profiler"] = []
_lock = threading.Lock()


def active_profilers() -> List["Profiler"]:
    """Profilers between ``start()`` and ``stop()`` (any scheduler state).
    While the list is non-empty ``_state.HOST_EVENTS`` holds
    :func:`_record_host_event`, which is how ``observability.span`` and
    ``RecordEvent`` reach the chrome export (docs/OBSERVABILITY.md,
    "Trace spans")."""
    with _lock:
        return list(_active_profilers)


def _record_host_event(name: str, start_ns: int, end_ns: int) -> None:
    """A finished host scope, onto every profiler in a RECORD window."""
    ev = _HostEvent(name, start_ns, end_ns, threading.get_ident())
    with _lock:
        for p in _active_profilers:
            if p._recording:
                p._events.append(ev)


def is_recording() -> bool:
    """True while any active profiler is in a RECORD window."""
    with _lock:
        return any(p._recording for p in _active_profilers)


class RecordEvent:
    """User scope: ``with RecordEvent("forward"):``.  Recorded on the host
    timeline of every recording profiler, and as the host event
    ``pdtpu.forward`` in the xplane of a live ``jax.profiler`` session
    (the same TraceAnnotation ``observability.span`` opens)."""

    def __init__(self, name: str, event_type=None):
        del event_type  # API compat
        self.name = name
        self._trace = None
        self._t0 = 0

    def begin(self):
        self._trace = jax.profiler.TraceAnnotation(TRACE_PREFIX + self.name)
        self._trace.__enter__()
        self._t0 = time.perf_counter_ns()

    def end(self):
        t1 = time.perf_counter_ns()
        if self._trace is not None:
            self._trace.__exit__(None, None, None)
            self._trace = None
        host = _obs_state.HOST_EVENTS[0]
        if host is not None:
            host(self.name, self._t0, t1)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-indexed state machine, mirroring paddle.profiler.make_scheduler:
    ``skip_first`` steps CLOSED, then cycles of (closed, ready, record)."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """Returns an on_trace_ready callback writing chrome-trace JSON into
    ``dir_name`` (reference: paddle.profiler.export_chrome_tracing)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{jax.process_index()}"
        path = os.path.join(dir_name, f"{name}_step{prof._step}.json")
        prof._export_chrome(path)
        return path

    return handler


class Profiler:
    """``paddle.profiler.Profiler`` parity.

    - host events from RecordEvent scopes (+ step markers from ``step()``)
    - device trace via jax.profiler start/stop into ``trace_dir`` (view with
      TensorBoard/XProf — the reference's timeline equivalent)
    - ``summary()`` prints an aggregated table of host scopes
    """

    def __init__(self, *, targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler=None, on_trace_ready=None, timer_only: bool = False,
                 trace_dir: Optional[str] = None):
        del targets  # single-backend stack; accepted for API parity
        if isinstance(scheduler, tuple):
            lo, hi = scheduler
            scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo, repeat=1)
        self._schedule = scheduler
        self._on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.trace_dir = trace_dir
        self._events: List[_HostEvent] = []
        self._step = 0
        self._step_t0: Optional[int] = None
        self._recording = False
        self._device_tracing = False
        self._state = ProfilerState.CLOSED
        # export dedupe: each record window fires on_trace_ready exactly
        # once.  Without this, a window ending in RECORD_AND_RETURN whose
        # next scheduled state is still recording (closed=0 back-to-back
        # cycles) was exported by step() AND re-exported by stop().
        self._window_exported = False

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        with _lock:
            _active_profilers.append(self)
            _obs_state.HOST_EVENTS[0] = _record_host_event
        self._apply_state(self._schedule(self._step) if self._schedule
                          else ProfilerState.RECORD)
        self._step_t0 = time.perf_counter_ns()
        return self

    def stop(self):
        # export only a window step() has not already exported (and that
        # has content): a stop() right after a RECORD_AND_RETURN boundary
        # used to re-fire on_trace_ready for the same window
        if self._recording and self._events and not self._window_exported \
                and self._on_trace_ready:
            self._on_trace_ready(self)
        self._apply_state(ProfilerState.CLOSED)
        with _lock:
            if self in _active_profilers:
                _active_profilers.remove(self)
            if not _active_profilers:
                _obs_state.HOST_EVENTS[0] = None

    def step(self):
        """Mark a train-step boundary; advances the scheduler."""
        t1 = time.perf_counter_ns()
        if self._recording and self._step_t0 is not None:
            self._events.append(_HostEvent(f"ProfileStep#{self._step}",
                                           self._step_t0, t1, 0))
        fired = False
        if self._state == ProfilerState.RECORD_AND_RETURN and self._on_trace_ready:
            self._on_trace_ready(self)
            self._window_exported = True
            fired = True
        self._step += 1
        self._step_t0 = t1
        if self._schedule:
            self._apply_state(self._schedule(self._step))
        if fired and self._recording:
            # back-to-back record windows (closed=0 cycles): the exported
            # window's events must not leak into — and be re-exported
            # with — the next window
            self._events = []
            self._window_exported = False

    def _apply_state(self, state: ProfilerState):
        was_recording = self._recording
        self._state = state
        self._recording = state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        if not was_recording and self._recording:
            self._window_exported = False
        if self.timer_only:
            return
        want_device = self._recording and self.trace_dir is not None
        if want_device and not self._device_tracing:
            jax.profiler.start_trace(self.trace_dir)
            self._device_tracing = True
        elif not want_device and self._device_tracing:
            jax.profiler.stop_trace()
            self._device_tracing = False
        if self._recording and not was_recording:
            self._events = []

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting ---------------------------------------------------------

    def _export_chrome(self, path: str):
        events = []
        for ev in self._events:
            events.append({"name": ev.name, "ph": "X", "pid": os.getpid(),
                           "tid": ev.tid, "ts": ev.start_ns / 1e3,
                           "dur": (ev.end_ns - ev.start_ns) / 1e3})
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return path

    def export(self, path: str, format: str = "json"):
        if format != "json":
            raise ValueError("only chrome-trace json export is supported")
        return self._export_chrome(path)

    def aggregate(self) -> List[Tuple[str, int, float, float]]:
        """[(name, count, total_ms, mean_ms)] sorted by total time."""
        acc: dict = defaultdict(lambda: [0, 0])
        for ev in self._events:
            a = acc[ev.name]
            a[0] += 1
            a[1] += ev.end_ns - ev.start_ns
        rows = [(n, c, t / 1e6, t / 1e6 / c) for n, (c, t) in acc.items()]
        return sorted(rows, key=lambda r: -r[2])

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms") -> str:
        del sorted_by, op_detail, thread_sep, time_unit
        rows = self.aggregate()
        w = max([len(r[0]) for r in rows] + [10])
        lines = [f"{'Name':<{w}}  {'Calls':>6}  {'Total(ms)':>10}  {'Avg(ms)':>10}",
                 "-" * (w + 32)]
        for n, c, tot, avg in rows:
            lines.append(f"{n:<{w}}  {c:>6}  {tot:>10.3f}  {avg:>10.3f}")
        table = "\n".join(lines)
        print(table)
        return table


def windowed_profiler(trace_dir: str, steps: Optional[int] = None,
                      on_trace_ready=None) -> Profiler:
    """A STARTED :class:`Profiler` recording host scopes + the device
    trace (``jax.profiler`` start/stop) into ``trace_dir`` — the
    bounded-capture entry the SLO-triggered capture arms
    (``observability.trace.SLOCapture``): the caller advances it with
    ``step()`` and ``stop()``s it after its window.  With ``steps``
    given, a ``make_scheduler`` window additionally closes the device
    trace on its own after that many ``step()`` calls (``stop()`` is
    still required to flush the host events / deregister)."""
    os.makedirs(trace_dir, exist_ok=True)
    sched = None
    if steps is not None:
        sched = make_scheduler(closed=0, ready=0, record=int(steps),
                               repeat=1)
    return Profiler(scheduler=sched, on_trace_ready=on_trace_ready,
                    trace_dir=trace_dir).start()


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)
