"""Trace spans: one vocabulary for the always-on JSONL stream, the
flight ring and whatever profiler session is live.

``with span("ckpt.save"):`` feeds, depending on what is enabled:

- the **flight recorder**: a ``span_begin`` breadcrumb at entry (the
  liveness beat the hang watchdog polls — recorded BEFORE the body so a
  span that never returns is visible as a stuck name, not silence);
- the **registry**: a ``span[<name>].ms`` duration histogram;
- the **event stream**: one ``span`` JSONL event on exit;
- the **profiler's host timeline**: a ``jax.profiler.TraceAnnotation``
  named ``pdtpu.<name>`` (``TRACE_PREFIX``: the one prefix by which a
  trace reducer tells the program's events from JAX's own).  TraceMe's
  own check (``is_enabled``) is the switch, so the event lands in the
  xplane of any live session — ``jax.profiler.start_trace``, the
  profiler server, ``SLOCapture``,
  ``paddle_tpu.profiler.Profiler(trace_dir=...)`` — on the clock the
  device events are on, and costs that one call when none is.  It is a
  HOST event: nothing is written into the device's lines (regions of a
  compiled program are ``jax.named_scope``s, ``observability/regions.py``);
- the **chrome export** of a recording ``paddle_tpu.profiler.Profiler``
  (``_state.HOST_EVENTS``, the sink ``RecordEvent`` writes to as well).

Pre-instrumented sites: ``jit.TrainStep`` steps (via StepMonitor, as
``emit=False`` spans — the ``step`` event already carries the numbers),
the serving loop's phases (``serve.*``, docs/OBSERVABILITY.md "Trace
spans"), ``distributed.Engine.fit`` / ``hapi.Model.fit`` epochs,
``ckpt`` save/load, eager collectives, and ``jit.save``/``jit.load``
AOT export.

Disabled cost: ``TraceMe.is_enabled()``, two clock reads and two falsy
checks on ``_state`` containers — no registry, no sink, no lock.
"""

from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation

from . import _state

__all__ = ["span", "spans_active", "TRACE_PREFIX"]

TRACE_PREFIX = "pdtpu."


def spans_active() -> bool:
    """True when a span would observe anything (telemetry span hook, a
    recording ``profiler.Profiler`` or a live profiler session).
    Per-call producers (eager collectives) use this as a fast path so
    the fully-disabled cost stays three falsy checks, with no
    span/f-string construction."""
    return (_state.SPAN[0] is not None
            or _state.HOST_EVENTS[0] is not None
            or TraceAnnotation.is_enabled())


class _SpanHook:
    """Installed in ``_state.SPAN[0]`` by ``observability.enable()``:
    routes span begin/ends into the recorder, registry, and sinks."""

    __slots__ = ("_reg", "_emit", "_rec")

    def __init__(self, registry=None, emit=None, recorder=None):
        self._reg = registry
        self._emit = emit
        self._rec = recorder

    def begin(self, name: str) -> None:
        rec = self._rec
        if rec is not None:
            rec.record("span_begin", name=name)

    def end(self, name: str, dur_ms: float, attrs: Optional[dict],
            emit: bool) -> None:
        if emit:
            if self._reg is not None:
                self._reg.histogram(f"span[{name}].ms").observe(dur_ms)
            if self._emit is not None:
                ev = {"event": "span", "name": name,
                      "ms": round(dur_ms, 3)}
                if attrs:
                    ev.update(attrs)
                self._emit(ev)   # lands in the ring via Telemetry.emit
                return
        rec = self._rec
        if rec is not None:
            rec.record("span_end", name=name, ms=round(dur_ms, 3))


class span:
    """Context manager: ``with span("name", **attrs): ...``.

    ``emit=False`` keeps the breadcrumbs and the profiler's host event
    but suppresses the JSONL event + registry histogram — used where
    another event already carries the numbers (TrainStep's ``step``
    event) or where a span is one of many per step (the serving loop's
    phases).
    """

    __slots__ = ("name", "attrs", "emit", "_t0", "_trace", "_hook")

    def __init__(self, name: str, emit: bool = True, **attrs):
        self.name = name
        self.attrs = attrs
        self.emit = emit
        self._trace = None
        self._hook = None
        self._t0 = 0

    def __enter__(self):
        self._hook = hook = _state.SPAN[0]
        if hook is not None:
            hook.begin(self.name)
        if TraceAnnotation.is_enabled():      # a profiler session is live
            self._trace = tm = TraceAnnotation(TRACE_PREFIX + self.name)
            tm.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        tm = self._trace
        if tm is not None:
            tm.__exit__(None, None, None)
            self._trace = None
        host = _state.HOST_EVENTS[0]
        if host is not None:
            host(self.name, self._t0, t1)
        hook = self._hook
        if hook is not None:
            hook.end(self.name, (t1 - self._t0) * 1e-6, self.attrs,
                     self.emit)
        return False
