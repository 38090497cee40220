"""Fused int4 dequant-in-matmul for weight-only serving (Pallas TPU).

Reference capability: the Cutlass ``fpA_intB`` GEMM specialised to int4
weights (paddle/phi/kernels/fusion/cutlass/fpA_intB_gemm — SURVEY §2.1
Cutlass row): activations in bf16, weights packed two int4 nibbles per
byte in HBM, dequantised on the fly inside the GEMM's inner loop.

Why a kernel at all: the XLA formulation (shift/stack/reshape then dot)
materialises the unpacked weight to HBM every decode step — measured
~8x slower than this kernel at 7B-shaped GEMVs (docs/BENCH.md round 5).
Decode is weight-bandwidth-bound, so the unpack must happen AFTER the
bytes leave HBM; here it runs on the VPU in VMEM.

TPU-native design — NOT a CUDA translation:

- **no nibble interleave**: ``_pack_int4`` stores row ``2i`` in the low
  nibble and row ``2i+1`` in the high nibble of byte-row ``i``.  Instead
  of reconstructing the interleaved (K, N) weight (a relayout Mosaic
  would have to shuffle), the contraction is split by parity:
  ``y = x[:, 0::2] @ lo(W) + x[:, 1::2] @ hi(W)`` — two dots per tile
  against the *byte-shaped* (K/2, N) layout, no shuffle anywhere.  The
  even/odd activation split is a cheap XLA strided slice on the (tiny)
  activation, outside the kernel.
- **sign extension via arithmetic shifts** on the int32-widened byte:
  ``lo = (b << 28) >> 28``, ``hi = b >> 4`` (the high nibble's shift
  doubles as floor-division, correct for negatives).  int8-lane shifts
  and ``pltpu.unpack_elementwise`` were both tried on v5e: the former
  crashes the Mosaic compiler, the latter measured no faster.
- grid is 1-D over N-column stripes with the full K2 contraction per
  step (fastest measured form); a 2-D (N, K2)-blocked grid with a VMEM
  f32 accumulator handles contractions too tall for one stripe's VMEM.

Measured reality on v5e (2026-07-31, 16-layer 4096<->11008 GEMV chain,
bytes-effective): this kernel ~130 GB/s vs XLA-int4 ~13 GB/s — but
XLA's native int8 GEMV path reaches ~315 GB/s, so **int8 remains the
speed-optimal serving point on v5e**; at M=1 the MXU is weight-load
bound (~128 elem/cycle regardless of M<128), a VPU mul-reduce
formulation measured slower still (80 GB/s), and pure tile-DMA caps at
~220 GB/s in Pallas here.  int4's role is CAPACITY: it halves weight
HBM so 13B-class models fit a 16 GiB chip, and this kernel makes that
mode usable instead of 10x-slower-than-int8 (docs/BENCH.md §serving
recommendation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp

DEFAULT_BLOCK_K2 = 1024     # 2-D path: packed rows per tile (= 2048 rows)
DEFAULT_BLOCK_N = 256
MAX_1D_K2 = 6144            # above this, full-K2 stripes blow VMEM


def _pick_block(n: int, preferred: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is <= preferred
    (Mosaic wants the last two block dims divisible by (8, 128) unless the
    block spans the full dim, which is the fallback)."""
    b = min(n, preferred) // 128 * 128
    while b >= 128:
        if n % b == 0:
            return b
        b -= 128
    return n


def _unpack(b):
    """(bk2, bn) packed bytes -> sign-extended (lo, bf16), (hi, bf16)."""
    b32 = b.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(b32, 28), 28)
    hi = jnp.right_shift(b32, 4)
    return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)


def _precision(dtype):
    # f32 activations must NOT be truncated to bf16 by the MXU default —
    # the XLA path this kernel replaces keeps full f32 (nibble values are
    # exact in bf16, so only the activation side needs HIGHEST)
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None)


def _kernel_1d(xe_ref, xo_ref, w_ref, s_ref, o_ref, *, out_dtype):
    lo, hi = _unpack(w_ref[...])
    cdt = xe_ref.dtype
    prec = _precision(cdt)
    acc = (jax.lax.dot(xe_ref[...], lo.astype(cdt), precision=prec,
                       preferred_element_type=jnp.float32)
           + jax.lax.dot(xo_ref[...], hi.astype(cdt), precision=prec,
                         preferred_element_type=jnp.float32))
    o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(out_dtype)


def _kernel_2d(xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_scr, *, k_blocks,
               out_dtype):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo, hi = _unpack(w_ref[...])
    cdt = xe_ref.dtype
    prec = _precision(cdt)
    acc_scr[...] += (
        jax.lax.dot(xe_ref[...], lo.astype(cdt), precision=prec,
                    preferred_element_type=jnp.float32)
        + jax.lax.dot(xo_ref[...], hi.astype(cdt), precision=prec,
                      preferred_element_type=jnp.float32))

    @pl.when(kb == k_blocks - 1)
    def _emit():
        o_ref[...] = (acc_scr[...] * s_ref[...].astype(jnp.float32)) \
            .astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("block_k2", "block_n",
                                             "interpret"))
def int4_matmul(x, packed, scale, block_k2: int = DEFAULT_BLOCK_K2,
                block_n: int = DEFAULT_BLOCK_N, interpret: bool = False):
    """``x @ dequant(packed) * scale`` with the unpack fused in VMEM.

    x: (M, K) float; packed: (K//2, N) int8 (``_pack_int4`` layout);
    scale: (N,) per-out-channel.  Returns (M, N) in ``x.dtype``.
    """
    m, k = x.shape
    k2, n = packed.shape
    if k != 2 * k2:
        raise ValueError(f"x K={k} vs packed rows {k2} (need K = 2*rows)")
    if scale.shape != (n,):
        raise ValueError(f"scale {scale.shape} != ({n},)")
    bn = _pick_block(n, block_n)
    xe = x[:, 0::2]                                    # (M, K2)
    xo = x[:, 1::2]
    s2 = scale.reshape(1, n)

    if k2 <= MAX_1D_K2:
        return pl.pallas_call(
            functools.partial(_kernel_1d, out_dtype=x.dtype),
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((m, k2), lambda jn: (0, 0)),
                pl.BlockSpec((m, k2), lambda jn: (0, 0)),
                pl.BlockSpec((k2, bn), lambda jn: (0, jn)),
                pl.BlockSpec((1, bn), lambda jn: (0, jn)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda jn: (0, jn)),
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            compiler_params=_pcp()(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="int4_matmul",
        )(xe, xo, packed, s2)

    bk2 = _pick_block(k2, block_k2)
    k_blocks = k2 // bk2
    return pl.pallas_call(
        functools.partial(_kernel_2d, k_blocks=k_blocks, out_dtype=x.dtype),
        grid=(n // bn, k_blocks),
        in_specs=[
            pl.BlockSpec((m, bk2), lambda jn, jk: (0, jk)),   # x even
            pl.BlockSpec((m, bk2), lambda jn, jk: (0, jk)),   # x odd
            pl.BlockSpec((bk2, bn), lambda jn, jk: (jk, jn)),  # packed w
            pl.BlockSpec((1, bn), lambda jn, jk: (0, jn)),    # scale
        ],
        out_specs=pl.BlockSpec((m, bn), lambda jn, jk: (0, jn)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=_pcp()(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="int4_matmul",
    )(xe, xo, packed, s2)
