"""Paged decode attention for TPU in Pallas (vLLM-style serving decode).

Reference capability: the reference serving stack's paged/block KV-cache
decode kernels (PaddleNLP inference on the fused decode CUDA kernels —
SURVEY §2.1 masked_multihead_attention row).

TPU-native design — NOT a translation of the CUDA kernel:
- the block table is a SCALAR-PREFETCH operand
  (``pltpu.PrefetchScalarGridSpec``), so each grid step's KV page is DMA'd
  straight from its pool slot via the BlockSpec index_map — the XLA
  formulation (``pool[tables]`` gather) materializes the gathered cache and
  is ~1000x slower on TPU;
- grid = (batch, pages); the page axis is innermost/sequential, so the
  online-softmax running (m, l, acc) lives in VMEM scratch across pages;
- one page block carries ALL kv heads (page, H_kv, D) — the per-head
  compute is a statically unrolled loop, keeping block shapes tile-aligned
  (Mosaic requires the last two block dims divisible by (8, 128) or full);
- GQA: the q heads of one kv head form a (G, D) tile — KV is never
  repeated.

Layouts: q (B, H, D); pools (NB, page, H_kv, D); tables (B, MB) int32;
lens (B,) int32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, lens_ref,           # scalar prefetch
            q_ref, k_ref, v_ref,            # blocks
            o_ref,                          # out block
            m_scr, l_scr, acc_scr,          # VMEM scratch
            *, page, scale, pages_per_seq, h_kv, g):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]

    @pl.when(ip * page < length)
    def _compute():
        pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        live = pos < length
        for hk in range(h_kv):                    # static unroll over kv heads
            rows = slice(hk * g, (hk + 1) * g)
            q = q_ref[0, hk].astype(jnp.float32)          # (G, D)
            k = k_ref[0, :, hk].astype(jnp.float32)       # (page, D)
            v = v_ref[0, :, hk].astype(jnp.float32)       # (page, D)
            # HIGHEST: full fp32 MXU passes — decode is bandwidth-bound, so
            # the extra matmul passes are free and kill the bf16 rounding
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
            s = jnp.where(live, s * scale, NEG_INF)       # (G, page)

            m_prev = m_scr[rows]                          # (G, 1)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[rows] = l_scr[rows] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
            acc_scr[rows] = acc_scr[rows] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            m_scr[rows] = m_new

    @pl.when(ip == pages_per_seq - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, lens, scale=None,
                    interpret=False):
    """q (B, H, D) × paged KV pools → (B, H, D).

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU CI)."""
    b, h, d = q.shape
    nb, page, h_kv, _ = k_pool.shape
    mb = block_tables.shape[1]
    g = h // h_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # (B, H_kv, G, D): q heads grouped under their kv head
    qg = q.reshape(b, h_kv, g, d)

    grid = (b, mb)

    def q_map(ib, ip, tables, lens_):
        return (ib, 0, 0, 0)

    def kv_map(ib, ip, tables, lens_):
        # Clamp dead pages (past the sequence length) to the last live page:
        # Pallas elides the re-fetch of an already-resident block, so short
        # sequences skip the dead DMA traffic — and padding entries of the
        # block table are never dereferenced as pool indices. The final
        # clip covers len==0 slots whose ENTIRE row is padding (often -1):
        # any in-range block is safe to fetch since compute is skipped.
        last_live = jnp.maximum(lens_[ib] - 1, 0) // page
        idx = tables[ib, jnp.minimum(ip, last_live)]
        return (jnp.clip(idx, 0, nb - 1), 0, 0, 0)

    def o_map(ib, ip, tables, lens_):
        return (ib, 0, 0)

    kernel = functools.partial(_kernel, page=page, scale=float(scale),
                               pages_per_seq=mb, h_kv=h_kv, g=g)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h_kv, g, d), q_map),
                pl.BlockSpec((1, page, h_kv, d), kv_map),
                pl.BlockSpec((1, page, h_kv, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, h_kv * g, d), o_map),
            scratch_shapes=[
                pltpu.VMEM((h_kv * g, 1), jnp.float32),
                pltpu.VMEM((h_kv * g, 1), jnp.float32),
                pltpu.VMEM((h_kv * g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv * g, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, lens, qg, k_pool, v_pool)
    return out.reshape(b, h, d)


def supported(q, k_pool, v_pool, block_tables, lens) -> bool:
    if q.ndim != 3 or k_pool.ndim != 4:
        return False
    b, h, d = q.shape
    h_kv = k_pool.shape[2]
    page = k_pool.shape[1]
    # page sizes from the v5e sweep (2026-07-30): 16 → 7.8ms, 64 → 2.1ms,
    # 128 → 1.7ms at B16/H32/2k ctx; page=32 triggers a Mosaic layout
    # pathology (1083ms) and is excluded
    page_ok = page == 16 or page % 64 == 0
    return (h % h_kv == 0 and d % 128 == 0 and page_ok
            and jax.default_backend() == "tpu")
