"""Recompile sentinel: count XLA compilations, attribute them, catch storms.

The classic TPU production failure is shape churn: a dynamic batch/seq
dimension (or a Python scalar leaking into a traced signature) makes
``jax.jit`` specialize per shape, and a job that benchmarked at 0.5 MFU
spends its life in the compiler — silently, because nothing in the
runtime counts compilations.  The reference framework surfaces this
through its profiler/monitor stack; jax exposes the raw signal via
``jax.monitoring`` (``/jax/core/compile/backend_compile_duration``
fires once per real backend compile, cache hits excluded — checked on
the installed jax 0.9.0 by tests/test_observability.py).

This module turns that signal into:

- per-site compile counters + duration histograms in the registry
  (site = the TrainStep / to_static callable that triggered tracing,
  threaded through a thread-local set by ``StepMonitor``);
- one ``compile`` JSONL event per compilation;
- a loud ``RecompileStormWarning`` + ``recompile_storm`` event when a
  site keeps compiling after its warmup allowance — >N compiles beyond
  warmup inside a rolling window.
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from typing import Optional

__all__ = ["RecompileSentinel", "RecompileStormWarning",
           "BACKEND_COMPILE_EVENT"]

# jax._src.dispatch.BACKEND_COMPILE_EVENT — the string is stable
# monitoring API surface; not imported from the private module.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

UNATTRIBUTED = "<unattributed>"


class RecompileStormWarning(RuntimeWarning):
    """A jit site kept recompiling after warmup — shape churn on TPU."""


class RecompileSentinel:
    """Listener on ``jax.monitoring`` compile-duration events.

    ``warmup`` compilations per site are expected (the initial trace, an
    accumulate-flag variant); each compile beyond that counts toward the
    storm window.  ``storm_threshold`` post-warmup compiles for one site
    within ``storm_window_s`` seconds trigger the warning, re-armed at
    most once per window per site so a pathological job warns every
    window, not every step.

    Unattributed compiles (eager ops, setup-phase jits outside any
    TrainStep/to_static call) are counted and emitted but excluded from
    storm WARNINGS by default — a normal startup does dozens of small
    one-off compiles that share the ``<unattributed>`` bucket and would
    trip any useful threshold.  ``storm_all_sites=True`` re-includes
    them.
    """

    def __init__(self, telemetry=None, registry=None, *, warmup: int = 1,
                 storm_threshold: int = 3, storm_window_s: float = 60.0,
                 storm_all_sites: bool = False):
        self._tel = telemetry
        self._reg = registry
        self.warmup = int(warmup)
        self.storm_threshold = int(storm_threshold)
        self.storm_window_s = float(storm_window_s)
        self.storm_all_sites = bool(storm_all_sites)
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._installed = False
        self._active = False
        self.total_compiles = 0
        self._per_site: dict = {}        # site -> compile count
        self._post_warmup: dict = {}     # site -> deque[t] inside window
        self._last_warn: dict = {}       # site -> t of last storm warning

    # -- site attribution --------------------------------------------------

    class _SiteScope:
        __slots__ = ("_sent", "_name", "_warmup")

        def __init__(self, sent, name, warmup):
            self._sent = sent
            self._name = name
            self._warmup = warmup

        def __enter__(self):
            stack = getattr(self._sent._tls, "stack", None)
            if stack is None:
                stack = self._sent._tls.stack = []
            stack.append((self._name, self._warmup))
            return self

        def __exit__(self, *exc):
            self._sent._tls.stack.pop()
            return False

    def site(self, name: str, *, warmup: bool = False) -> "_SiteScope":
        """Context manager: compiles fired inside are attributed to
        ``name`` (a TrainStep/to_static call site).  ``warmup=True``
        marks the compiles as EXPECTED — they count and attribute like
        any other but never enter the storm window, so a process that
        legitimately warms the same site repeatedly (bench scenarios,
        one engine per test, a re-built engine after evacuation) stays
        quiet while genuine shape churn outside a warmup scope still
        warns."""
        return self._SiteScope(self, name, warmup)

    def current_site(self) -> str:
        stack = getattr(self._tls, "stack", None)
        return stack[-1][0] if stack else UNATTRIBUTED

    def _current_scope(self):
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else (UNATTRIBUTED, False)

    # -- lifecycle ---------------------------------------------------------

    def install(self) -> None:
        if not self._installed:
            import jax
            jax.monitoring.register_event_duration_secs_listener(self._on_event)
            self._installed = True
        self._active = True

    def uninstall(self) -> None:
        """Deactivate and unregister the listener."""
        self._active = False
        if self._installed:
            import jax
            jax.monitoring.unregister_event_duration_listener(
                self._on_event)
            self._installed = False

    # -- the listener ------------------------------------------------------

    def _on_event(self, event: str, duration_secs: float, **kw) -> None:
        if not self._active or event != BACKEND_COMPILE_EVENT:
            return
        site, expected = self._current_scope()
        now = time.monotonic()
        storm = None
        with self._lock:
            self.total_compiles += 1
            n = self._per_site.get(site, 0) + 1
            self._per_site[site] = n
            if n > self.warmup and not expected \
                    and (site != UNATTRIBUTED or self.storm_all_sites):
                window = self._post_warmup.setdefault(site, deque())
                window.append(now)
                while window and now - window[0] > self.storm_window_s:
                    window.popleft()
                if (len(window) >= self.storm_threshold
                        and now - self._last_warn.get(site, -1e30)
                        >= self.storm_window_s):
                    self._last_warn[site] = now
                    storm = len(window)
            total = self.total_compiles
        if self._reg is not None:
            self._reg.counter("compile.count").inc()
            self._reg.counter(f"compile[{site}].count").inc()
            self._reg.histogram("compile.duration_ms").observe(
                duration_secs * 1e3)
            # scrapeable per-site attribution: the bracket=pair grammar
            # renders as recompiles_total{site="..."} on /metrics (both
            # the engine surface and the cluster fleet fold), where the
            # compile[<site>].count spelling above becomes a label on
            # the *compile_count* family keyed by the dotted head.  The
            # reserved grammar chars ("[],=") are squashed exactly like
            # aggregate._label_value so wire snapshots round-trip.
            site_l = site
            for ch in "[],=":
                site_l = site_l.replace(ch, "_")
            self._reg.counter(f"recompiles_total[site={site_l}]").inc()
        if self._tel is not None:
            self._tel.emit({"event": "compile", "site": site,
                            "duration_ms": round(duration_secs * 1e3, 3),
                            "site_count": n, "count": total})
        if storm is not None:
            msg = (f"recompile storm: {site} compiled {storm} times beyond "
                   f"its {self.warmup}-compile warmup within "
                   f"{self.storm_window_s:.0f}s — a traced shape or static "
                   "arg is churning (dynamic batch/seq dim, Python scalar "
                   "in the signature). Every compile stalls the whole "
                   "slice; pad shapes to fixed buckets or hoist the "
                   "changing value out of the traced signature. See "
                   "docs/OBSERVABILITY.md.")
            if self._tel is not None:
                self._tel.emit({"event": "recompile_storm", "site": site,
                                "compiles_after_warmup": storm,
                                "window_s": self.storm_window_s,
                                "site_count": n})
            warnings.warn(msg, RecompileStormWarning, stacklevel=2)

    # -- introspection -----------------------------------------------------

    def compiles(self, site: Optional[str] = None) -> int:
        if site is None:
            return self.total_compiles
        return self._per_site.get(site, 0)
