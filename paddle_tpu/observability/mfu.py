"""MFU accounting shared by bench.py and the runtime StepMonitor.

THE single source of the flops-per-token formula and the per-chip peak
table: bench numbers (BENCH_r*.json) and runtime telemetry events agree
by construction because both call these functions — a change here moves
both, a change nowhere else can split them.

Accounting convention (docs/BENCH.md): causal-LM training flops/token =
``6N + 6·L·h·T`` — 6N for the parameter matmuls (fwd+bwd), causal
attention credited at half the s² matmul.  Recompute is never credited
(an honest MFU carries the remat tax).
"""

from __future__ import annotations

import functools
from typing import Optional

__all__ = ["PEAK_BF16_FLOPS", "peak_flops", "causal_lm_flops_per_token",
           "dense_flops_per_token", "flops_per_token_of"]


PEAK_BF16_FLOPS = {
    # per-chip peak bf16 FLOP/s
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e
    "cpu": 1e12,             # nominal, CI only
}


@functools.lru_cache(maxsize=1)
def peak_flops() -> float:
    """Peak bf16 FLOP/s of device 0's chip kind (1e12 nominal on CPU).
    A kind the table does not know is an error, never a default: an MFU
    over the wrong peak is a wrong number under a device metric's name."""
    import jax
    kind = jax.devices()[0].device_kind
    best = max((k for k in PEAK_BF16_FLOPS if kind.startswith(k)),
               key=len, default=None)
    if best is None:
        raise ValueError(
            f"no peak FLOP/s known for device kind {kind!r}; add it to "
            "observability.mfu.PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[best]


def causal_lm_flops_per_token(n_params: int, num_layers: int,
                              hidden_size: int, seq_len: int) -> float:
    """Causal-attention-aware model flops per trained token: 6N + 6·L·h·T."""
    return 6.0 * n_params + 6.0 * num_layers * hidden_size * seq_len


def dense_flops_per_token(n_params: int) -> float:
    """Attention-less fallback (6N) for models without a transformer
    config — an MFU floor, exact for pure-MLP workloads."""
    return 6.0 * n_params


def flops_per_token_of(model, seq_len: Optional[int]) -> Optional[float]:
    """Best-effort flops/token for an arbitrary model.

    Transformer configs (``model.cfg`` with ``num_params``/
    ``num_hidden_layers``/``hidden_size`` — the llama/gpt shape) get the
    full causal formula; any other Layer gets the 6N floor; a model with
    no countable parameters returns None (the step event then simply
    omits ``mfu``).
    """
    cfg = getattr(model, "cfg", None)
    if (cfg is not None and seq_len and callable(getattr(cfg, "num_params", None))
            and hasattr(cfg, "num_hidden_layers") and hasattr(cfg, "hidden_size")):
        return causal_lm_flops_per_token(cfg.num_params(),
                                         cfg.num_hidden_layers,
                                         cfg.hidden_size, seq_len)
    params = getattr(model, "parameters", None)
    if callable(params):
        try:
            n = sum(int(p.size) for p in params())
        except Exception:
            return None
        return dense_flops_per_token(n) if n else None
    return None
