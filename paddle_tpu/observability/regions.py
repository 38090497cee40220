"""The region vocabulary of the traced programs.

A region is a ``jax.named_scope`` opened while a program is TRACED: the
name becomes a component of the ``op_name`` metadata of every HLO
instruction traced under it, forward, backward and recompute alike, and
costs nothing at run time.  The device trace names an event by its
instruction, so a reader that holds the compiled text (instruction ->
``op_name``) can give every microsecond of device time to the region
that owns it: an event belongs to the INNERMOST region on its path
(``optimizer/clip/...`` is ``clip``), and an XLA fusion carries its
root's path.

Every causal-LM model file uses the same names, on the cached (serving)
and the uncached (training) path alike, so that one reader serves every
model family; ``jit.TrainStep`` adds ``forward`` (the parent of the
model's regions), ``clip`` and ``optimizer``.  A new model file uses
these names and adds none (docs/OBSERVABILITY.md, "Trace spans").
"""

from __future__ import annotations

import jax

__all__ = ["REGIONS", "region"]

REGIONS = (
    "embed",          # token (and learned position) lookup
    "norm",           # RMSNorm / LayerNorm, wherever it is called from
    "attn_proj",      # qkv, RoPE or positions, output projection, residual
    "attn_core",      # flash / ragged / XLA attention, whichever runs
    "mlp",            # the feed-forward block (an expert block too)
    "lm_head_loss",   # head matmul, softmax cross-entropy or the sampler
    "clip",           # global-norm gradient clip
    "optimizer",      # the parameter update
)


def region(name: str):
    """``with region("mlp"):`` — a ``jax.named_scope`` of the vocabulary."""
    if name not in REGIONS:
        raise ValueError(f"{name!r} is not a region; the vocabulary is "
                         f"{REGIONS} (observability/regions.py)")
    return jax.named_scope(name)
