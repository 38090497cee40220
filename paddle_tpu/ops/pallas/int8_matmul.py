"""Fused int8 dequant-in-matmul for weight-only serving (Pallas TPU) —
the int8 sibling of ``int4_matmul.py``, same stripe design minus the
nibble split.

Why a kernel when XLA's native int8 GEMV is already strong (int4_matmul
docstring, v5e ~315 GB/s): the XLA path widens int8→bf16 through a
separate convert whose fusion placement XLA decides — at some serving
shapes it materializes the widened weight tile to HBM, and the
per-out-channel scale epilogue is a second pass.  This kernel pins the
contract: HBM streams the RAW int8 bytes, the widening happens on the
VPU in VMEM, the scale multiply rides the output tile — and the
autotuner owns the stripe shape per geometry instead of XLA's heuristics
(tools/tuned_configs.json; re-sweep with ``python tools/autotune.py``).
``weight_only_linear`` gates dispatch to decode-sized token counts where
the weight stream IS the roofline; prefill keeps XLA.

Layout: x (M, K) float; w (K, N) int8 (``weight_quantize`` int8 layout,
no packing); scale (N,) f32 per-out-channel.  1-D grid over N-column
stripes with the full-K contraction per step; a 2-D (N, K)-blocked grid
with a VMEM f32 accumulator handles contractions too tall for one
stripe's VMEM (same structure as the int4 kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp
from .. import tuning
from ._common import mxu_precision as _precision
from ._common import pick_block as _pick_block

DEFAULT_BLOCK_K = 2048      # 2-D path: contraction rows per tile
DEFAULT_BLOCK_N = 256
MAX_1D_K = 8192             # above this, full-K stripes blow VMEM


def _kernel_1d(x_ref, w_ref, s_ref, o_ref, *, out_dtype):
    cdt = x_ref.dtype
    acc = jax.lax.dot(x_ref[...], w_ref[...].astype(cdt),
                      precision=_precision(cdt),
                      preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(out_dtype)


def _kernel_2d(x_ref, w_ref, s_ref, o_ref, acc_scr, *, k_blocks,
               out_dtype):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cdt = x_ref.dtype
    acc_scr[...] += jax.lax.dot(x_ref[...], w_ref[...].astype(cdt),
                                precision=_precision(cdt),
                                preferred_element_type=jnp.float32)

    @pl.when(kb == k_blocks - 1)
    def _emit():
        o_ref[...] = (acc_scr[...] * s_ref[...].astype(jnp.float32)) \
            .astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "block_n",
                                             "interpret"))
def int8_matmul(x, w, scale, block_k=None, block_n=None,
                interpret: bool = False):
    """``x @ w.astype(float) * scale`` with the int8 widening fused in
    VMEM.  x: (M, K) float; w: (K, N) int8; scale: (N,) per-out-channel.
    Returns (M, N) in ``x.dtype``."""
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"x K={k} vs weight rows {k2}")
    if scale.shape != (n,):
        raise ValueError(f"scale {scale.shape} != ({n},)")
    if block_k is None or block_n is None:
        cfg = tuning.tuned_config("int8_matmul",
                                  tuning.geom_key(k=k, n=n))
        block_k = block_k or cfg.get("block_k", DEFAULT_BLOCK_K)
        block_n = block_n or cfg.get("block_n", DEFAULT_BLOCK_N)
    bn = _pick_block(n, block_n)
    s2 = scale.reshape(1, n)

    if k <= MAX_1D_K:
        return pl.pallas_call(
            functools.partial(_kernel_1d, out_dtype=x.dtype),
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((m, k), lambda jn: (0, 0)),
                pl.BlockSpec((k, bn), lambda jn: (0, jn)),
                pl.BlockSpec((1, bn), lambda jn: (0, jn)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda jn: (0, jn)),
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            compiler_params=_pcp()(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="int8_matmul",
        )(x, w, s2)

    bk = _pick_block(k, block_k)
    k_blocks = k // bk
    return pl.pallas_call(
        functools.partial(_kernel_2d, k_blocks=k_blocks,
                          out_dtype=x.dtype),
        grid=(n // bn, k_blocks),
        in_specs=[
            pl.BlockSpec((m, bk), lambda jn, jk: (0, jk)),
            pl.BlockSpec((bk, bn), lambda jn, jk: (jk, jn)),
            pl.BlockSpec((1, bn), lambda jn, jk: (0, jn)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda jn, jk: (0, jn)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=_pcp()(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="int8_matmul",
    )(x, w, s2)
