"""Fused transformer MLP for TPU in Pallas — gate/up matmul, activation,
and down projection in ONE kernel (no HBM round-trip for the (T, I)
intermediate).

Why a kernel: the unfused LlamaMLP runs three XLA matmuls with the
``silu(g)·u`` elementwise between them — the (T, I) gate/up activations
(I = 2.75·H for Llama) round-trip HBM twice per layer, and at training
shapes that intermediate is the layer's largest transient.  XLA does not
fuse ACROSS matmuls, so the only way to keep ``h = silu(x@Wg)·(x@Wu)``
in VMEM until the down projection consumes it is one kernel (the
FlashFuser "fusing memory-bound epilogues around the matmuls" recipe,
PAPERS.md).

One body of mathematics, ``acc += act(x@Wg[:, blk]) · (x@Wu[:, blk]) @
Wd[blk, :]`` with a float32 accumulator in VMEM, under two loop orders.
Which one runs is read from the call, never set:

- **token-tiled** (no ``n_live``, or a ``(T, H)`` too large to hold):
  grid = (token-tiles, I-blocks), the I axis innermost/sequential, a
  ``(bt, H)`` accumulator across the I-blocks.  The x tile's index is
  constant across the inner axis, so Pallas fetches it once per token
  tile; the weight blocks' index changes every grid step, so **every
  token tile reads all three matrices**: ``T / bt`` weight reads a call.
  That is right where the tile is compute-bound (training: ``bt`` 256
  rows sit on the chip's ridge) and is the path of ``TrainStep``,
  ``generate`` and ``chip_smoke.py``.
- **weight-stationary** (``n_live`` given: the serving step, which knows
  how many of its ``(B, C)`` lanes hold a token and has put those rows
  first): grid = (I-blocks,), the only pipelined axis; ``x``, the
  accumulator and the output are whole in VMEM, and inside a grid step a
  loop of ``ceil(n_live / LIVE_TILE)`` iterations (a dynamic trip count:
  the scalar arrives by scalar prefetch) multiplies one token tile each.
  **Each weight byte is read exactly once a call** whatever ``T`` is,
  token tiles past ``n_live`` cost nothing, and rows past ``n_live``
  come back zero.  A decode step of a few live tokens then costs the
  weights' one crossing of HBM, not ``T`` rows of matmul.
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
# token rows one loop iteration of the weight-stationary path multiplies
LIVE_TILE = 128
# the weight-stationary path runs where its estimate (x, the accumulator
# and the output whole beside the weight blocks) fits this much of
# VMEM_LIMIT: the estimate is within 1 MiB of what Mosaic allocates
LIVE_VMEM_BUDGET = 44 * 2 ** 20


def _round_up(n: int, q: int) -> int:
    return -(-n // q) * q


def _swiglu_block(x, wg_ref, wu_ref, wd_ref):
    """One token tile against one I-block: the tile's float32 share of
    the output — the mathematics both loop orders accumulate."""
    prec = _precision(x.dtype)
    g = jax.lax.dot(x, wg_ref[...], precision=prec,
                    preferred_element_type=jnp.float32)
    u = jax.lax.dot(x, wu_ref[...], precision=prec,
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jax.lax.dot(h, wd_ref[...], precision=prec,
                       preferred_element_type=jnp.float32)


def _swiglu_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_scr,
                   *, i_blocks, out_dtype):
    ii = pl.program_id(1)

    @pl.when(ii == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += _swiglu_block(x_ref[...], wg_ref, wu_ref, wd_ref)

    @pl.when(ii == i_blocks - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(out_dtype)


def _swiglu_live_kernel(n_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_scr,
                        *, i_blocks, tile, out_dtype):
    """Weight-stationary order: one grid step per I-block, and under the
    block's weights a loop over the token tiles that hold a live row."""
    ii = pl.program_id(0)
    n = n_ref[0]

    @pl.when(ii == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def one_tile(t, carry):
        rows = pl.ds(pl.multiple_of(t * tile, tile), tile)
        acc_scr[rows, :] += _swiglu_block(x_ref[rows, :], wg_ref, wu_ref,
                                          wd_ref)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n, tile), one_tile, None)

    @pl.when(ii == i_blocks - 1)
    def _emit():
        # rows past n_live are zero, not whatever the dead lanes held:
        # tiles the loop never touched still hold the init's zeros, the
        # last live tile's tail is masked here
        row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        for t in range(acc_scr.shape[0] // tile):
            rows = pl.ds(t * tile, tile)
            o_ref[rows, :] = jnp.where(row + t * tile < n, acc_scr[rows, :],
                                       0.0).astype(out_dtype)


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


def _live_vmem_estimate(tp, tile, bi, h, itemsize):
    """Scoped VMEM of the weight-stationary path: the three weight blocks
    double-buffered, x and the output whole and single-buffered (their
    block never changes), the whole float32 accumulator, and one token
    tile's temporaries (the x tile, g/u with their rounded product; the
    tile's float32 share is added into the accumulator as it is made).
    Deviceless v5e compiles need 40-42 MiB at (512, 4096) x 512 columns
    where this says 41.6, and 42-44 at a tile of 256 (43.3)."""
    weights = 2 * 3 * h * bi * itemsize
    resident = 2 * tp * h * itemsize + tp * h * 4
    temps = tile * h * itemsize + 2 * tile * bi * 4 + tile * bi * itemsize
    return weights + resident + temps


def _live_tile(t):
    return min(LIVE_TILE, _round_up(t, 16))


def _live_fits(t, bi, h, itemsize) -> bool:
    tile = _live_tile(t)
    return _live_vmem_estimate(_round_up(t, tile), tile, bi, h,
                               itemsize) <= LIVE_VMEM_BUDGET


def holds_live(x, w1) -> bool:
    """Whether a call with ``n_live`` takes the weight-stationary order:
    ``(T, H)`` whole in VMEM, three times over (x, the float32
    accumulator, the output), beside the weight blocks.  The serving
    step's (512, 4096) is; a training batch's (8192, 4096) is not."""
    t, h = x.shape
    itemsize = x.dtype.itemsize
    _, bi = _blocks(t, h, w1.shape[1], None, None, itemsize)
    return _live_fits(t, bi, h, itemsize)


def _pad_tokens(x, bt):
    t = x.shape[0]
    rem = t % bt
    if rem:
        x = jnp.pad(x, ((0, bt - rem), (0, 0)))
    return x


def fused_swiglu_mlp(x, w_gate, w_up, w_down, n_live=None, block_t=None,
                     block_i=None, interpret: bool = False):
    """``(x @ Wg → silu) · (x @ Wu) @ Wd`` in one kernel.

    x: (T, H); w_gate/w_up: (H, I); w_down: (I, H).  Returns (T, H) in
    ``x.dtype``.  ``n_live`` (an int32 scalar, traced) says that only
    rows ``[0, n_live)`` of ``x`` hold a token: with it, and a ``(T, H)``
    that VMEM can hold, the weight-stationary order runs (module
    docstring) and rows past ``n_live`` come back zero; else every row is
    computed.  ``interpret=True`` runs the Pallas interpreter (CPU CI
    equivalence tests).
    """
    t, h = x.shape
    i = w_gate.shape[1]
    bt, bi = _blocks(t, h, i, block_t, block_i, x.dtype.itemsize)
    i_blocks = i // bi
    # the I-blocks are the token-tiled path's own, so both orders add the
    # same float32 partial sums in the same order: a live row's result
    # does not depend on which path computed it
    if n_live is not None and _live_fits(t, bi, h, x.dtype.itemsize):
        tile = _live_tile(t)
        xp = _pad_tokens(x, tile)
        tp = xp.shape[0]
        whole = pl.BlockSpec((tp, h), lambda ii, n: (0, 0),
                             pipeline_mode=pl.Buffered(1))
        out = pl.pallas_call(
            functools.partial(_swiglu_live_kernel, i_blocks=i_blocks,
                              tile=tile, out_dtype=x.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(i_blocks,),
                in_specs=[
                    whole,
                    pl.BlockSpec((h, bi), lambda ii, n: (0, ii)),
                    pl.BlockSpec((h, bi), lambda ii, n: (0, ii)),
                    pl.BlockSpec((bi, h), lambda ii, n: (ii, 0)),
                ],
                out_specs=whole,
                scratch_shapes=[pltpu.VMEM((tp, h), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((tp, h), x.dtype),
            compiler_params=_pcp()(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name="fused_swiglu_mlp",
        )(jnp.minimum(n_live, t).astype(jnp.int32).reshape(1), xp,
          w_gate, w_up, w_down)
        return out[:t]
    xp = _pad_tokens(x, bt)
    tp = xp.shape[0]
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
