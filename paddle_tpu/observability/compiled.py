"""Compiled-artifact ledger: what each XLA program costs to build and run.

The stack measures wall time everywhere (step events, serve.step_ms,
span histograms) but never confronts it with what the compiled program
*should* cost.  XLA already knows: every ``MeshExecutable`` carries
``cost_analysis()`` (flops, bytes accessed) and ``memory_analysis()``
(argument/output/temp bytes) — this module captures both, once per real
backend compile, into per-program rows keyed to the recompile
sentinel's site attribution.  On top of the rows:

- an **analytic roofline**: a small overridable chip-spec table (peak
  FLOP/s + HBM GB/s; CPU gets a measured stand-in) turns each program's
  flops/bytes into a compute-bound or bandwidth-bound minimum step
  time, so ``serve.roofline.*`` / ``train.roofline.*`` gauges can say
  how close measured wall time sits to the hardware limit;
- **HBM accounting inputs**: per-program ``temp``/``argument``/
  ``output`` bytes feed the ``serve.hbm.*`` gauges next to the actual
  pool buffer sizes.

Capture point: ``jax._src.interpreters.pxla.MeshComputation.compile``
— the one choke point both normal jit dispatch and AOT lowering flow
through (checked on the installed jax 0.9.0: tests/test_compiled_obs.py
pins one ledger row per sentinel compile).  Wrapping it sees exactly one
executable per real backend compile (cache hits never reach it), so the
ledger adds ZERO compiles and changes no behavior; the wrapper is only
installed while telemetry is enabled (``observability.enable()``), so
the disabled cost is literally nothing.

Like ``aggregate.py``/``sinks.py`` this module loads standalone (no
package import, no relative imports, jax optional) so offline tools can
reuse the chip-spec table and roofline math.  The FLOP/s column must
stay consistent with ``mfu.PEAK_BF16_FLOPS`` — a unit test pins them
together.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

__all__ = ["CompiledArtifactLedger", "CHIP_SPECS", "chip_spec",
           "roofline"]

UNATTRIBUTED = "<unattributed>"     # mirrors recompile.UNATTRIBUTED

# Per-chip peak bf16 FLOP/s and HBM bandwidth (GB/s).  FLOP/s numbers
# are THE same values as observability/mfu.py's PEAK_BF16_FLOPS (pinned
# by tests/test_compiled_obs.py); bandwidths are the published per-chip
# HBM numbers.  Keys are device_kind prefixes, longest match wins.
CHIP_SPECS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_gbps": 819.0},   # v5e
    "TPU v5e": {"peak_flops": 197e12, "hbm_gbps": 819.0},
    "TPU v5p": {"peak_flops": 459e12, "hbm_gbps": 2765.0},
    "TPU v5": {"peak_flops": 459e12, "hbm_gbps": 2765.0},
    "TPU v4": {"peak_flops": 275e12, "hbm_gbps": 1228.0},
    "TPU v6 lite": {"peak_flops": 918e12, "hbm_gbps": 1640.0},  # v6e
    # CPU: nominal flops (CI only, matches mfu.py); bandwidth is a
    # measured stand-in (see _measured_cpu_gbps) so CPU rooflines are
    # at least the right order of magnitude rather than pure fiction.
    "cpu": {"peak_flops": 1e12, "hbm_gbps": None},
}

_CPU_GBPS = [None]  # measured once per process


def _measured_cpu_gbps() -> float:
    """Measured CPU memory bandwidth stand-in: time a few large
    bytearray copies (stdlib-only).  Cached per process; clamped to a
    sane floor so a loaded CI machine can't produce absurd rooflines."""
    if _CPU_GBPS[0] is not None:
        return _CPU_GBPS[0]
    n = 32 * 1024 * 1024                       # 32 MiB, past L2
    src = bytearray(n)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        dst = bytes(src)                       # one read + one write
        dt = time.perf_counter() - t0
        best = min(best, dt)
        del dst
    gbps = (2.0 * n / best) / 1e9 if best > 0 else 10.0
    _CPU_GBPS[0] = max(1.0, min(gbps, 1000.0))
    return _CPU_GBPS[0]


def chip_spec(kind: Optional[str] = None, override: Optional[dict] = None
              ) -> dict:
    """Resolve the roofline spec for a device kind.

    ``kind=None`` asks jax for device 0's ``device_kind`` (``"cpu"``
    when jax is absent — the standalone-load contract).  ``override``
    merges user-supplied ``peak_flops``/``hbm_gbps`` on top.  A kind the
    table does not know is an error, never a default — unless the
    override supplies both numbers, the way to describe a new chip.
    Returns ``{"kind", "peak_flops", "hbm_gbps"}``.
    """
    if kind is None:
        try:
            import jax
        except ImportError:
            kind = "cpu"
        else:
            kind = jax.devices()[0].device_kind
    override = {k: v for k, v in (override or {}).items() if v is not None}
    best = max((k for k in CHIP_SPECS if kind.startswith(k)), key=len,
               default=None)
    if best is None and not {"peak_flops", "hbm_gbps"} <= set(override):
        raise ValueError(
            f"no chip spec known for device kind {kind!r}; add it to "
            "observability.compiled.CHIP_SPECS with its source, or pass "
            "both peak_flops and hbm_gbps as chip_spec_override")
    spec = CHIP_SPECS[best] if best is not None else {}
    out = {"kind": kind, "peak_flops": spec.get("peak_flops"),
           "hbm_gbps": spec.get("hbm_gbps")}
    out.update(override)
    if out["hbm_gbps"] is None:
        out["hbm_gbps"] = _measured_cpu_gbps()
    return out


def roofline(flops: float, bytes_accessed: float, spec: dict) -> dict:
    """Analytic minimum execution time for one program under ``spec``.

    ``t_compute = flops / peak_flops``, ``t_memory = bytes /
    (hbm_gbps * 1e9)``; the program cannot finish faster than the
    larger of the two.  Returns ``{"min_ms", "compute_ms", "memory_ms",
    "bound"}`` where ``bound`` is ``"compute"`` or ``"bandwidth"``
    (ties go to compute — the flattering read for a matmul-heavy
    program sitting exactly on the ridge).
    """
    peak = float(spec.get("peak_flops") or 1e12)
    gbps = float(spec.get("hbm_gbps") or 1.0)
    t_c = float(flops) / peak
    t_m = float(bytes_accessed) / (gbps * 1e9)
    bound = "compute" if t_c >= t_m else "bandwidth"
    return {"min_ms": max(t_c, t_m) * 1e3, "compute_ms": t_c * 1e3,
            "memory_ms": t_m * 1e3, "bound": bound}


class CompiledArtifactLedger:
    """Per-compile cost/memory rows with site attribution.

    ``install()`` wraps ``pxla.MeshComputation.compile`` (jax-optional:
    a no-op when jax is absent); every real backend compile then lands
    one row via :meth:`record_executable`.  ``uninstall()`` restores
    the original method — ``observability.disable()`` calls it, so the
    wrapper never outlives the telemetry session.
    """

    def __init__(self, sentinel=None, telemetry=None,
                 spec: Optional[dict] = None):
        self._sentinel = sentinel
        self._tel = telemetry
        self._spec = spec               # resolved lazily on first row
        self._rows: List[dict] = []
        self._hbm: dict = {}
        self._lock = threading.Lock()
        self._installed = False
        self._orig_compile = None

    # -- chip spec ---------------------------------------------------------

    @property
    def spec(self) -> dict:
        if self._spec is None or "peak_flops" not in self._spec:
            self._spec = chip_spec(override=self._spec)
        return self._spec

    # -- lifecycle ---------------------------------------------------------

    def install(self) -> None:
        """Wrap the one compile choke point.  Idempotent; silently a
        no-op without jax (standalone contract)."""
        if self._installed:
            return
        try:
            from jax._src.interpreters import pxla
        except Exception:
            return
        orig = pxla.MeshComputation.compile
        ledger = self

        def _ledger_compile(comp, *args, **kw):
            t0 = time.perf_counter()
            executable = orig(comp, *args, **kw)
            try:
                ledger.record_executable(
                    executable,
                    program=str(getattr(comp, "_name", "") or "<unnamed>"),
                    compile_ms=(time.perf_counter() - t0) * 1e3)
            except Exception:
                # accounting must never break a compile
                pass
            return executable

        self._orig_compile = orig
        pxla.MeshComputation.compile = _ledger_compile
        self._installed = True

    def uninstall(self) -> None:
        if not self._installed:
            return
        try:
            from jax._src.interpreters import pxla
            if self._orig_compile is not None:
                pxla.MeshComputation.compile = self._orig_compile
        except Exception:
            pass
        self._installed = False
        self._orig_compile = None

    # -- capture -----------------------------------------------------------

    def record_executable(self, executable, *, program: str = "<unnamed>",
                          compile_ms: float = 0.0) -> dict:
        """Extract one row from a compiled executable (duck-typed:
        ``cost_analysis()`` / ``memory_analysis()`` both optional, so a
        backend without them still yields the compile-ms row)."""
        site = UNATTRIBUTED
        if self._sentinel is not None:
            try:
                site = self._sentinel.current_site()
            except Exception:
                pass
        row = {"site": site, "program": program,
               "compile_ms": round(float(compile_ms), 3),
               "flops": 0.0, "bytes_accessed": 0.0,
               "argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
               "alias_bytes": 0, "generated_code_bytes": 0,
               "peak_bytes": 0}
        try:
            ca = executable.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            row["flops"] = float(ca.get("flops", 0.0) or 0.0)
            row["bytes_accessed"] = float(
                ca.get("bytes accessed", 0.0) or 0.0)
        except Exception:
            pass
        try:
            ma = executable.memory_analysis()
            for attr, key in (
                    ("argument_size_in_bytes", "argument_bytes"),
                    ("output_size_in_bytes", "output_bytes"),
                    ("temp_size_in_bytes", "temp_bytes"),
                    ("alias_size_in_bytes", "alias_bytes"),
                    ("generated_code_size_in_bytes",
                     "generated_code_bytes")):
                row[key] = int(getattr(ma, attr, 0) or 0)
            # live-at-peak estimate: everything resident while the
            # program runs, minus donated/aliased input bytes counted
            # twice on the argument AND output side
            row["peak_bytes"] = max(0, row["argument_bytes"]
                                    + row["output_bytes"]
                                    + row["temp_bytes"]
                                    + row["generated_code_bytes"]
                                    - row["alias_bytes"])
        except Exception:
            pass
        rl = roofline(row["flops"], row["bytes_accessed"], self.spec)
        row["min_ms"] = round(rl["min_ms"], 6)
        row["bound"] = rl["bound"]
        with self._lock:
            self._rows.append(row)
        tel = self._tel
        if tel is not None:
            try:
                tel.emit({"event": "compiled_artifact", **row})
            except Exception:
                pass
        return row

    # -- introspection -----------------------------------------------------

    def snapshot(self) -> List[dict]:
        """Copy of all rows (dicts are shallow-copied: callers mutate
        freely, e.g. the postmortem writer)."""
        with self._lock:
            return [dict(r) for r in self._rows]

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)

    def rows_for(self, site: str) -> List[dict]:
        with self._lock:
            return [dict(r) for r in self._rows if r["site"] == site]

    def min_ms_for(self, site: str) -> Optional[float]:
        """Roofline minimum step time for ``site``'s dominant program
        (the row with the largest analytic minimum — a site that
        compiled variants runs ONE of them per step, and the dominant
        one is the steady-state step).  None if the site never
        compiled or its programs carried no cost analysis."""
        best = None
        with self._lock:
            for r in self._rows:
                if r["site"] == site and r["min_ms"] > 0:
                    if best is None or r["min_ms"] > best:
                        best = r["min_ms"]
        return best

    # -- HBM gauge snapshot (for exit reports / postmortems) ---------------

    def set_hbm(self, stats: dict) -> None:
        """Attach the latest ``{pool: bytes}`` HBM snapshot (engine
        warmup publishes it) so postmortems and exit reports carry the
        memory picture without re-touching device buffers."""
        with self._lock:
            self._hbm = dict(stats)

    @property
    def hbm(self) -> dict:
        with self._lock:
            return dict(self._hbm)
