"""Grouped BGMV for batched multi-LoRA decode (Pallas TPU) —
``out[b] = x[b] @ A[idx[b]] @ B[idx[b]]`` in one pass per batch slot
(docs/SERVING.md "Multi-LoRA", docs/KERNELS.md).

Why a kernel when the XLA gather+einsum composition is correct: the
composition materializes the gathered ``(B, d_in, r)``/``(B, r, d_out)``
adapter copies to HBM before the batched matmuls, and the rank-r
``(B, C, r)`` intermediate round-trips HBM between the shrink and
expand.  Per-slot adapter traffic is the whole cost of multi-LoRA at
decode (the base GEMV already streams the big weights), so this kernel
pins the contract instead: the scalar-prefetched adapter index DMAs
each slot's ``A_i``/``B_i`` block STRAIGHT from its stack slot via the
BlockSpec index map (no gathered copy), the shrink's ``(C, r)``
intermediate lives in VMEM scratch across the expand stripes, and
slot 0 — the reserved base no-op — skips both matmuls outright and
writes zeros, so base-only lanes pay ~nothing.

Mixed adapter ids within one batch are native: the grid is
``(batch, d_out-stripes)`` and every slot fetches its own blocks.

Layout: x ``(B, C, d_in)`` float; a ``(N, d_in, r)``; b
``(N, r, d_out)``; idx ``(B,)`` int32.  Out ``(B, C, d_out)`` in
``x.dtype``.  Numerics contract (pinned by the interpret-mode tests in
tests/test_lora.py against ``incubate.nn.functional._lora_bgmv_ref``):
both dots accumulate f32, the rank-r intermediate rounds to ``x.dtype``
between them — exactly the XLA composition's op order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import tuning
from ._common import mxu_precision as _precision
from ._common import pick_block as _pick_block

DEFAULT_BLOCK_O = 2048      # d_out columns per expand stripe


def _kernel(idx_ref,                       # scalar prefetch
            x_ref, a_ref, b_ref,           # blocks
            o_ref,                         # out block
            h_scr,                         # (C, r) VMEM scratch
            *, out_dtype):
    ib = pl.program_id(0)
    jo = pl.program_id(1)
    ad = idx_ref[ib]
    cdt = x_ref.dtype

    @pl.when(jnp.logical_and(ad != 0, jo == 0))
    def _shrink():
        # (C, d_in) @ (d_in, r) → f32; rounds to x.dtype at the expand
        # read below (the composition's intermediate dtype)
        h_scr[...] = jax.lax.dot(x_ref[0], a_ref[0].astype(cdt),
                                 precision=_precision(cdt),
                                 preferred_element_type=jnp.float32)

    @pl.when(ad != 0)
    def _expand():
        o_ref[0] = jax.lax.dot(h_scr[...].astype(cdt),
                               b_ref[0].astype(cdt),
                               precision=_precision(cdt),
                               preferred_element_type=jnp.float32) \
            .astype(out_dtype)

    @pl.when(ad == 0)
    def _base_noop():
        # slot 0 is the reserved exact no-op: no matmuls, exact zeros
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("block_o", "interpret"))
def grouped_bgmv(x, a, b, idx, block_o=None, interpret: bool = False):
    """``x[b] @ a[idx[b]] @ b[idx[b]]`` per batch slot, shrink+expand
    fused with the rank-r intermediate VMEM-resident.  Returns
    ``(B, C, d_out)`` in ``x.dtype``; ``idx == 0`` rows are exact
    zeros."""
    bsz, c, d_in = x.shape
    n, d_in2, r = a.shape
    n2, r2, d_out = b.shape
    if (n, r) != (n2, r2) or d_in != d_in2:
        raise ValueError(
            f"stack mismatch: x(..., {d_in}) a{a.shape} b{b.shape}")
    if idx.shape != (bsz,):
        raise ValueError(f"idx {idx.shape} != ({bsz},)")
    if block_o is None:
        cfg = tuning.tuned_config("lora_bgmv",
                                  tuning.geom_key(h=d_in, r=r, o=d_out))
        block_o = cfg.get("block_o", DEFAULT_BLOCK_O)
    bo = _pick_block(d_out, block_o)

    def x_map(ib, jo, idx_):
        return (ib, 0, 0)

    def a_map(ib, jo, idx_):
        return (idx_[ib], 0, 0)

    def b_map(ib, jo, idx_):
        return (idx_[ib], 0, jo)

    def o_map(ib, jo, idx_):
        return (ib, 0, jo)

    return pl.pallas_call(
        functools.partial(_kernel, out_dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, d_out // bo),
            in_specs=[
                pl.BlockSpec((1, c, d_in), x_map),
                pl.BlockSpec((1, d_in, r), a_map),
                pl.BlockSpec((1, r, bo), b_map),
            ],
            out_specs=pl.BlockSpec((1, c, bo), o_map),
            scratch_shapes=[pltpu.VMEM((c, r), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, c, d_out), x.dtype),
        interpret=interpret,
        name="lora_bgmv",
    )(idx, x, a, b)


def supported(x, a, b) -> bool:
    """Shape gate for the dispatch path: MXU-aligned projection dims
    (the serving geometries — hidden/head multiples of 128) on a real
    TPU; everything else takes the XLA composition."""
    if x.ndim != 3 or a.ndim != 3 or b.ndim != 3:
        return False
    d_in, d_out, r = x.shape[-1], b.shape[-1], a.shape[-1]
    return (d_in % 128 == 0 and d_out % 128 == 0 and r % 8 == 0
            and jax.default_backend() == "tpu")
