"""Fused transformer MLP for TPU in Pallas — gate/up matmul, activation,
and down projection in ONE pass over the weights (no HBM round-trip for
the (T, I) intermediate).

Why a kernel: the unfused LlamaMLP runs three XLA matmuls with the
``silu(g)·u`` elementwise between them — the (T, I) gate/up activations
(I = 2.75·H for Llama) round-trip HBM twice per layer, and at training
shapes that intermediate is the layer's largest transient.  XLA does not
fuse ACROSS matmuls, so the only way to keep ``h = silu(x@Wg)·(x@Wu)``
in VMEM until the down projection consumes it is one kernel (the
FlashFuser "fusing memory-bound epilogues around the matmuls" recipe,
PAPERS.md).

TPU-native design:

- grid = (token-tiles, I-blocks); the I axis is innermost/sequential, so
  a (bt, H) f32 accumulator lives in VMEM scratch across I-blocks:
  ``acc += act(x@W1[:, blk]) @ W2[blk, :]`` — each weight byte is read
  exactly once, the intermediate never leaves VMEM;
- the x tile's BlockSpec index is constant across the inner axis, so
  Pallas elides its re-fetch (one HBM read of the hidden states per
  token tile).

Block shapes come from tools/tuned_configs.json (ops.tuning, resolved at
trace time) with safe defaults; sweep with ``python tools/autotune.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp
from .. import tuning
from ._common import VMEM_BUDGET, VMEM_LIMIT
from ._common import mxu_precision as _precision
from ._common import pick_block as _pick_block

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_I = 512


def _round_up(n: int, q: int) -> int:
    return -(-n // q) * q


def _swiglu_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_scr,
                   *, i_blocks, out_dtype):
    ii = pl.program_id(1)

    @pl.when(ii == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]
    prec = _precision(x.dtype)
    g = jax.lax.dot(x, wg_ref[...], precision=prec,
                    preferred_element_type=jnp.float32)
    u = jax.lax.dot(x, wu_ref[...], precision=prec,
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_scr[...] += jax.lax.dot(h, wd_ref[...], precision=prec,
                                preferred_element_type=jnp.float32)

    @pl.when(ii == i_blocks - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(out_dtype)


def _blocks(t, h, i, block_t, block_i, itemsize):
    """Resolve (bt, bi) — explicit args win, then tuned configs (trace
    time, ops.tuning), then defaults shrunk to the VMEM budget."""
    cfg = {}
    if block_t is None or block_i is None:
        cfg = tuning.tuned_config("fused_swiglu_mlp",
                                  tuning.geom_key(h=h, i=i))
    # the token axis is padded up to a block multiple (zeros, sliced off
    # after), so bt only needs sublane alignment — odd T is fine
    bt = max(8, (block_t or cfg.get("block_t", DEFAULT_BLOCK_T)) // 8 * 8)
    bt = min(bt, _round_up(t, 8))
    bi = _pick_block(i, block_i or cfg.get("block_i", DEFAULT_BLOCK_I), 128)
    while _vmem_estimate(bt, bi, h, itemsize) > VMEM_BUDGET and bi > 128:
        nbi = _pick_block(i, bi // 2, 128)
        if nbi >= bi:
            break   # no smaller divisor exists (e.g. I not 128-aligned)
        bi = nbi
    return bt, bi


def _vmem_estimate(bt, bi, h, itemsize):
    """Scoped VMEM Mosaic allocates for one grid cell: every pipelined
    operand is double-buffered (x tile, out tile, the three weight
    blocks), plus the f32 accumulator, the f32 g/u tiles with their
    rounded product, and the rounded copy of the accumulator at emit."""
    pipelined = 2 * (2 * bt * h + 3 * h * bi) * itemsize
    acc = bt * h * 4
    temps = 2 * bt * bi * 4 + bt * bi * itemsize + bt * h * itemsize
    return pipelined + acc + temps


def _pad_tokens(x, bt):
    t = x.shape[0]
    rem = t % bt
    if rem:
        x = jnp.pad(x, ((0, bt - rem), (0, 0)))
    return x


def fused_swiglu_mlp(x, w_gate, w_up, w_down, block_t=None, block_i=None,
                     interpret: bool = False):
    """``(x @ Wg → silu) · (x @ Wu) @ Wd`` in one kernel pass.

    x: (T, H); w_gate/w_up: (H, I); w_down: (I, H).  Returns (T, H) in
    ``x.dtype``.  ``interpret=True`` runs the Pallas interpreter (CPU
    CI equivalence tests).
    """
    t, h = x.shape
    i = w_gate.shape[1]
    bt, bi = _blocks(t, h, i, block_t, block_i, x.dtype.itemsize)
    xp = _pad_tokens(x, bt)
    tp = xp.shape[0]
    i_blocks = i // bi
    out = pl.pallas_call(
        functools.partial(_swiglu_kernel, i_blocks=i_blocks,
                          out_dtype=x.dtype),
        grid=(tp // bt, i_blocks),
        in_specs=[
            pl.BlockSpec((bt, h), lambda it, ii: (it, 0)),
            pl.BlockSpec((h, bi), lambda it, ii: (0, ii)),
            pl.BlockSpec((h, bi), lambda it, ii: (0, ii)),
            pl.BlockSpec((bi, h), lambda it, ii: (ii, 0)),
        ],
        out_specs=pl.BlockSpec((bt, h), lambda it, ii: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, h), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, h), jnp.float32)],
        compiler_params=_pcp()(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="fused_swiglu_mlp",
    )(xp, w_gate, w_up, w_down)
    return out[:t]


def supported(x, w1, w2) -> bool:
    """Mosaic-shape gate: 128-aligned H/I, fp dtypes, and block geometry
    inside the VMEM budget — the blocks the kernel will actually use."""
    if x.ndim != 2 or w1.ndim != 2 or w2.ndim != 2:
        return False
    h, i = w1.shape
    if h % 128 or i % 128 or x.shape[1] != h:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    bt, bi = _blocks(max(x.shape[0], 8), h, i, None, None,
                     x.dtype.itemsize)
    return _vmem_estimate(bt, bi, h, x.dtype.itemsize) <= VMEM_BUDGET
