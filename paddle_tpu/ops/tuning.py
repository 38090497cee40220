"""Tuned-config registry: block shapes / fusion switches / serving knobs
swept by ``tools/autotune.py`` and persisted to ``tools/tuned_configs.json``.

The contract (docs/KERNELS.md "Autotuning"):

- configs are READ-ONLY at runtime and resolved AT TRACE TIME (kernel
  wrappers) or at construction time (``serving.Engine``) — never per
  step.  A mutation of the store after the first trace is deliberately
  ignored: jit caches key on the resolved values, which is exactly the
  serving zero-recompile contract.  pdtpu-lint's retrace-hazard rule
  recognizes lookups through :func:`tuned_config` as this sanctioned
  idiom and still flags per-step (in-loop) reads feeding a compiled
  callable (docs/ANALYSIS.md).
- the store is keyed ``{backend: {op: {geometry_key: config}}}`` so one
  committed file carries cpu and tpu winners side by side; a missing
  entry means "use the kernel's built-in default", never an error.
- re-tuning: ``python tools/autotune.py --update`` re-sweeps and
  rewrites the file; a running process picks it up only on restart (or
  an explicit :func:`reload` BEFORE any trace).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

_CONFIG_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tools", "tuned_configs.json")

# load-once store: [None] until the first lookup, then the parsed dict
# for the process lifetime (trace-time-frozen by design — see module
# docstring).  Env override PDTPU_TUNED_CONFIGS points at an alternate
# file ("" disables tuning entirely: every lookup returns {}).
_STORE = [None]


def config_path() -> str:
    return os.environ.get("PDTPU_TUNED_CONFIGS", _CONFIG_PATH)


def _load() -> Dict[str, Any]:
    if _STORE[0] is None:
        path = config_path()
        data: Dict[str, Any] = {}
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                data = {}   # a torn/absent file means defaults, not a crash
        _STORE[0] = data if isinstance(data, dict) else {}
    return _STORE[0]


def reload() -> None:
    """Drop the cached store so the next lookup re-reads the file.  Only
    meaningful BEFORE anything traces — already-compiled programs keep
    the configs they resolved (documented contract)."""
    _STORE[0] = None


def _backend() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:
        return "cpu"


def tuned_config(op: str, key: Optional[str] = None,
                 backend: Optional[str] = None) -> Dict[str, Any]:
    """The sanctioned tuned-config lookup: winners for ``op`` at geometry
    ``key`` on ``backend`` (default: the current jax backend), or ``{}``.

    Call this at trace/construction time and bake the values into the
    compiled program; never call it per dispatch step (pdtpu-lint flags
    that).  ``key=None`` returns the op's whole per-geometry table."""
    store = _load().get(backend or _backend(), {})
    table = store.get(op, {})
    if not isinstance(table, dict):
        return {}
    if key is None:
        return table
    cfg = table.get(key, {})
    return cfg if isinstance(cfg, dict) else {}


def fusion_enabled(mode: str, op: str, key: Optional[str] = None) -> bool:
    """Resolve a model's ``fused_ops`` mode for one op at trace time.

    ``"off"`` → never; ``"on"`` → always (the entry point still falls
    back to its XLA composition where the kernel cannot serve);
    ``"auto"`` → only when the kernel dispatch is live (TPU backend, no
    active mesh, ``use_pallas_kernels`` flag) AND the tuned configs do
    not veto it (``{"enabled": false}`` recorded by the autotuner when
    the sweep measured the fusion as a loss for this geometry)."""
    if mode == "off" or not mode:
        return False
    if mode == "on":
        return True
    if mode != "auto":
        raise ValueError(f"fused_ops={mode!r}: expected on|off|auto")
    from . import dispatch
    if dispatch.get(op) is None:
        return False
    from .pallas import _active_mesh
    if _active_mesh() is not None:
        return False
    cfg = tuned_config(op, key) if key else {}
    return bool(cfg.get("enabled", True))


def geom_key(**dims: int) -> str:
    """Canonical geometry key: sorted ``name`` ``value`` pairs joined by
    underscores (``geom_key(h=1024, i=2816) -> 'h1024_i2816'``) — ONE
    formula shared by the kernels and the autotuner so their keys agree
    by construction."""
    return "_".join(f"{k}{dims[k]}" for k in sorted(dims))
