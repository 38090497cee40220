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

TPU-native design:
- block tables + span starts/lens are SCALAR-PREFETCH operands; the
  pools stay in HBM (``memory_space=ANY``) and the kernel fetches KV
  through the block table with its own DMAs;
- grid = (batch,): ONE grid step per slot.  Inside it a loop walks the
  slot's live context in KV blocks of ``_pages_per_block`` pages (128
  positions where a page is 16 or 64), ``ceil((starts+lens) / block)``
  times: a dead slot runs no iteration and finalises to zeros, and a
  table entry past the live pages costs nothing and is never
  dereferenced.  Block ``i + 1`` loads into the second buffer while
  block ``i`` computes; the online-softmax running (m, l, acc) lives in
  VMEM scratch across blocks;
- one KV block carries ALL kv heads; the q rows of one kv head form a
  (C*G, D) tile — span rows and GQA groups share the MXU pass, KV is
  never repeated.  The MXU takes q, k, v and p in the pool's own dtype
  and accumulates in float32 (float32 pools stay float32 end to end);
- rows ``j >= lens[b]`` are DEAD: their scores mask to -inf past
  position ``starts[b] + j``, and because block 0 is always visited
  first for a live slot their running max is finite, so they accumulate
  bounded garbage the caller discards (the engine reads logits only at
  row ``lens[b]-1``).

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
# VMEM for the double-buffered K and V blocks (2 pools x 2 buffers), and
# the key positions one MXU pass covers: a block is as many pages as
# fill the budget, at most BLOCK_TOKENS of them
KV_VMEM_BUDGET = 4 * 2 ** 20
BLOCK_TOKENS = 128


def _pages_per_block(page, h_kv, d, dtype, mb):
    """Pages of K (and of V) fetched and attended per loop iteration."""
    page_bytes = page * h_kv * d * jnp.dtype(dtype).itemsize
    by_vmem = KV_VMEM_BUDGET // (4 * page_bytes)
    return max(1, min(by_vmem, BLOCK_TOKENS // page, mb))


def _kernel(*refs, page, ppb, scale, g, skip):
    # scalar prefetch (tables, starts, lens and, with ``skip``, skips);
    # the q block; the pools, left in HBM; the out block; the
    # double-buffered KV blocks; the online-softmax state
    (tables_ref, starts_ref, lens_ref), refs = refs[:3], refs[3:]
    skips_ref = None
    if skip:
        skips_ref, refs = refs[0], refs[1:]
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
     m_scr, l_scr, acc_scr) = refs
    b = pl.program_id(0)
    rows = acc_scr.shape[1]
    blk = ppb * page
    start = starts_ref[b]
    total = start + lens_ref[b]          # tokens in the pool for this slot
    n_blk = pl.cdiv(total, blk)          # 0 for a dead slot
    last_page = jnp.maximum(total - 1, 0) // page

    def copies(i, buf):
        """The DMAs of KV block ``i`` into buffer ``buf``, page by page
        through the block table.  Pages past the slot's last live one
        re-read that page: a table's padding is never dereferenced, and
        what lands there is finite and masked."""
        out = []
        for p in range(ppb):
            idx = tables_ref[b, jnp.minimum(i * ppb + p, last_page)]
            dst = pl.ds(p * page, page)
            out.append(pltpu.make_async_copy(
                k_hbm.at[idx], k_buf.at[buf, dst], sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[idx], v_buf.at[buf, dst], sems.at[1, buf]))
        return out

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_blk > 0)
    def _first():
        for cp in copies(0, 0):
            cp.start()

    precision = (jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32
                 else None)
    # span index j of each query row (row = j * g + gq)
    j_row = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 0) // g
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)

    def block(i, carry):
        buf = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blk)
        def _next():
            for cp in copies(i + 1, 1 - buf):
                cp.start()

        for cp in copies(i, buf):
            cp.wait()
        # causal vs the pool: row j sees positions [0, start + j]
        pos = i * blk + col
        live = pos <= start + j_row
        if skips_ref is not None:
            # ... but for the rest of the page that position skips[b]
            # lies in: rows of a summary page that are not for this
            # query (``eva_paged_attend``); nothing where it is aligned
            lo = skips_ref[b]
            live = live & ((pos < lo) | (pos >= pl.cdiv(lo, page) * page))
        q = q_ref[0].astype(k_buf.dtype)                  # (H_kv, C*G, D)
        k = jnp.swapaxes(k_buf[buf], 0, 1)                # (H_kv, blk, D)
        v = jnp.swapaxes(v_buf[buf], 0, 1)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32,
                                precision=precision)
        s = jnp.where(live, s * scale, NEG_INF)           # (H_kv, C*G, blk)
        m_prev = m_scr[...]                               # (H_kv, C*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=precision)
        m_scr[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blk, block, None)
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, starts, lens,
                           scale=None, interpret=False, skips=None,
                           name="ragged_paged_attention"):
    """q (B, C, H, D) spans × paged KV pools → (B, C, H, D).

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU CI).

    ``skips`` and ``name`` serve EVA's step
    (``incubate.nn.functional.eva_paged_attend``), which walks a table of
    summary pages then window pages through this kernel.  ``skips``
    ``(B,)`` is a fourth scalar-prefetch operand: slot ``b`` sees nothing
    from position ``skips[b]`` to the end of the page it lies in (the
    rows of the last summary page that are not yet for this query; none
    where ``skips[b]`` is a multiple of the page).  Without it the
    program is the one a full-attention model has always had.  ``name``
    is the ``pallas_call``'s, hence its device events':
    ``eva_ragged_paged_attention`` there, so that a trace tells them from
    a full-attention model's.
    """
    b, c, h, d = q.shape
    _, page, h_kv, _ = k_pool.shape
    mb = block_tables.shape[1]
    g = h // h_kv
    rows = c * g
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    ppb = _pages_per_block(page, h_kv, d, k_pool.dtype, mb)
    # (B, H_kv, C*G, D): span rows grouped under their kv head, row = j*g+gq
    qg = q.reshape(b, c, h_kv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, h_kv, rows, d)

    def slot_map(ib, *prefetch):
        return (ib, 0, 0, 0)

    prefetch = (block_tables, starts, lens) \
        + (() if skips is None else (skips,))
    kernel = functools.partial(_kernel, page=page, ppb=ppb,
                               scale=float(scale), g=g,
                               skip=skips is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h_kv, rows, d), slot_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h_kv, rows, d), slot_map),
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page, h_kv, d), k_pool.dtype),
                pltpu.VMEM((2, ppb * page, h_kv, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((h_kv, rows, 1), jnp.float32),
                pltpu.VMEM((h_kv, rows, 1), jnp.float32),
                pltpu.VMEM((h_kv, rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, rows, d), q.dtype),
        interpret=interpret,
        name=name,
    )(*prefetch, qg, k_pool, v_pool)
    return out.reshape(b, h_kv, c, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, h, d)


# Mosaic grants a kernel 16 MiB of scoped VMEM unless it asks for more
# (``_common.py``), and this kernel does not ask
SCOPED_VMEM = 16 * 2 ** 20


def vmem_bytes(c, h, h_kv, d, page, mb, dtype) -> int:
    """What one grid step holds in VMEM: the double-buffered K and V
    blocks and their head-major copies, the q and o blocks (each
    double-buffered by the pipeline), the running (m, l, acc) and the
    score-sized temporaries (s, p, the mask).  With 32 kv heads a page
    is 128 KiB a pool, so the blocks alone take 6 MiB of it."""
    item = jnp.dtype(dtype).itemsize
    rows = c * (h // h_kv)
    block = _pages_per_block(page, h_kv, d, dtype, mb) * page * h_kv * d * item
    qo = h_kv * rows * d * item
    state = h_kv * rows * (d + 2) * 4
    scores = h_kv * rows * min(BLOCK_TOKENS, mb * page) * 4
    return 4 * block + 2 * block + 4 * qo + state + 3 * scores


def supported(q, k_pool, v_pool, block_tables, starts, lens) -> bool:
    if q.ndim != 4 or k_pool.ndim != 4:
        return False
    b, c, h, d = q.shape
    h_kv = k_pool.shape[2]
    page = k_pool.shape[1]
    if h % h_kv:
        return False
    # deviceless v5e compiles refuse a span of 256 at GQA 32/8 and of 128
    # at 32 kv heads (PR 26, PR 32): what a grid step holds passes the
    # scoped limit there
    if vmem_bytes(c, h, h_kv, d, page, block_tables.shape[1],
                  k_pool.dtype) > SCOPED_VMEM:
        return False
    # same page-size gates as the decode kernel (v5e sweep 2026-07-30:
    # page=32 triggers a Mosaic layout pathology and is excluded)
    page_ok = page == 16 or page % 64 == 0
    # a page is DMA'd whole: Mosaic slices one out of a 16-bit pool only
    # where the kv heads fill the (H_kv, D) face's HBM tiles (deviceless
    # v5e compiles, PR 26: 1, 3, 6 and 12 kv heads are refused)
    heads_ok = (jnp.dtype(k_pool.dtype).itemsize >= 4 or h_kv % 8 == 0
                or h_kv in (2, 4))
    return (h % h_kv == 0 and d % 128 == 0 and page_ok and heads_ok
            and jax.default_backend() == "tpu")
