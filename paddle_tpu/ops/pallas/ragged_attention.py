"""Ragged paged attention for TPU in Pallas — ONE kernel for the whole
serving batch (PAPERS.md "Ragged Paged Attention").

Each batch slot carries a token SPAN against the paged KV pools: either a
chunked-prefill segment (``lens[b] > 1``), a single decode token
(``lens[b] == 1``), or nothing (``lens[b] == 0`` — idle/dead slot).  The
span's k/v has already been scattered into the pool at positions
``[starts[b], starts[b] + lens[b])``; query row ``j`` (position
``starts[b] + j``) attends over pool positions ``[0, starts[b] + j]`` —
the cached prefix plus the causal part of its own span.  This is what
lets chunked prefill and decode share one fixed-shape dispatch instead of
one bucket-prefill program per length plus a separate decode program.

TPU-native design (shared with decode_attention.py):
- block tables + span starts/lens are SCALAR-PREFETCH operands, so each
  grid step's KV page is DMA'd straight from its pool slot via the
  BlockSpec index_map;
- grid = (batch, pages); the page axis is innermost/sequential, so the
  online-softmax running (m, l, acc) lives in VMEM scratch across pages;
  pages at or past ``starts+lens`` are skipped (``pl.when``), so a
  mostly-decode batch does decode-sized work;
- one page block carries ALL kv heads; the q rows of one kv head form a
  (C*G, D) tile — span rows and GQA groups share the MXU pass, KV is
  never repeated;
- rows ``j >= lens[b]`` are DEAD: their scores mask to -inf everywhere,
  and because page 0 is always visited first for a live slot their
  running max is finite, so they accumulate bounded garbage the caller
  discards (the engine reads logits only at row ``lens[b]-1``).

Layouts: q (B, C, H, D); pools (NB, page, H_kv, D); tables (B, MB) int32;
starts/lens (B,) int32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, starts_ref, lens_ref,   # scalar prefetch
            q_ref, k_ref, v_ref,                # blocks
            o_ref,                              # out block
            m_scr, l_scr, acc_scr,              # VMEM scratch
            *, page, scale, pages_per_seq, h_kv, g, c):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = starts_ref[b]
    total = start + lens_ref[b]          # tokens in the pool for this slot

    @pl.when(ip * page < total)
    def _compute():
        rows = c * g
        # pool position of each key column in this page
        pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        # span index j of each query row (row = j * g + gq)
        j_row = jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0) // g
        # causal vs the pool: row j sees positions [0, start + j]
        live = pos <= start + j_row
        for hk in range(h_kv):               # static unroll over kv heads
            rr = slice(hk * rows, (hk + 1) * rows)
            q = q_ref[0, hk].astype(jnp.float32)          # (C*G, D)
            k = k_ref[0, :, hk].astype(jnp.float32)       # (page, D)
            v = v_ref[0, :, hk].astype(jnp.float32)       # (page, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
            s = jnp.where(live, s * scale, NEG_INF)       # (C*G, page)

            m_prev = m_scr[rr]                            # (C*G, 1)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[rr] = l_scr[rr] * alpha + jnp.sum(p, axis=1,
                                                    keepdims=True)
            acc_scr[rr] = acc_scr[rr] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            m_scr[rr] = m_new

    @pl.when(ip == pages_per_seq - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, starts, lens,
                           scale=None, interpret=False):
    """q (B, C, H, D) spans × paged KV pools → (B, C, H, D).

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU CI).
    """
    b, c, h, d = q.shape
    nb, page, h_kv, _ = k_pool.shape
    mb = block_tables.shape[1]
    g = h // h_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # (B, H_kv, C*G, D): span rows grouped under their kv head, row = j*g+gq
    qg = q.reshape(b, c, h_kv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, h_kv, c * g, d)

    grid = (b, mb)

    def q_map(ib, ip, tables, starts_, lens_):
        return (ib, 0, 0, 0)

    def kv_map(ib, ip, tables, starts_, lens_):
        # Clamp dead pages (past the span's end) to the last live page:
        # Pallas elides the re-fetch of an already-resident block, so
        # short contexts skip the dead DMA traffic — and padding entries
        # of the block table are never dereferenced as pool indices.
        last_live = jnp.maximum(starts_[ib] + lens_[ib] - 1, 0) // page
        idx = tables[ib, jnp.minimum(ip, last_live)]
        return (jnp.clip(idx, 0, nb - 1), 0, 0, 0)

    def o_map(ib, ip, tables, starts_, lens_):
        return (ib, 0, 0)

    kernel = functools.partial(_kernel, page=page, scale=float(scale),
                               pages_per_seq=mb, h_kv=h_kv, g=g, c=c)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h_kv, c * g, d), q_map),
                pl.BlockSpec((1, page, h_kv, d), kv_map),
                pl.BlockSpec((1, page, h_kv, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, h_kv * c * g, d), o_map),
            scratch_shapes=[
                pltpu.VMEM((h_kv * c * g, 1), jnp.float32),
                pltpu.VMEM((h_kv * c * g, 1), jnp.float32),
                pltpu.VMEM((h_kv * c * g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv * c * g, d), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_tables, starts, lens, qg, k_pool, v_pool)
    return out.reshape(b, h_kv, c, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, h, d)


def supported(q, k_pool, v_pool, block_tables, starts, lens) -> bool:
    if q.ndim != 4 or k_pool.ndim != 4:
        return False
    b, c, h, d = q.shape
    h_kv = k_pool.shape[2]
    page = k_pool.shape[1]
    # same page-size gates as the decode kernel (v5e sweep 2026-07-30:
    # page=32 triggers a Mosaic layout pathology and is excluded)
    page_ok = page == 16 or page % 64 == 0
    return (h % h_kv == 0 and d % 128 == 0 and page_ok
            and jax.default_backend() == "tpu")
