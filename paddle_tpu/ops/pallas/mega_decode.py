"""Decode megakernel: the whole ragged decoder-layer attention block —
RMSNorm → QKV → rotate-half RoPE → ragged paged attention → O-proj
(+residual) — in ONE persistent-style Pallas dispatch per layer.

Why a kernel: decode at bs≤8 is dispatch/bandwidth-bound.  The fusion
library (PR 9) stopped at per-projection kernels, so the ragged step
still issues ~5 dispatches per decoder layer — norm+qkv+rope, the span
KV scatter, the ragged attention kernel, the O-proj matmul, the
residual add — each round-tripping activations through HBM.  Here the
hidden-state tile is read once; the normed projection, the roped q/k,
the online-softmax attention state and the attention output all stay
VMEM-resident between stages (FlashFuser / CUTLASS FA2 tier —
PAPERS.md), and the only HBM traffic is the x tile in, the pool pages
in, and the (o, span-k, span-v) tiles out.

Structure (grid = (batch, pages); page axis innermost/sequential):

- ``ip == 0``: rms-norm the slot's span tile, run the q/k/v projections
  against VMEM-resident weights, apply the selector-matmul rotate-half
  rope (fused_norm_qkv's formulation — no layout ops), and park the
  results in VMEM scratch.  The span's roped k / v are also emitted as
  kernel OUTPUTS: the caller scatters them into the paged pools with
  the same ``_paged_span_write`` the composition uses, so the pool
  update is byte-identical and dead-slot rows still drop on their OOB
  block ids.
- prefix pages (``ip * page < start``): the online-softmax pass of
  ragged_attention.py over the slot's CACHED prefix only (positions
  ``< start``), all GQA rows of one kv head sharing the MXU pass; the
  block-table index map clamps skipped/dead pages to the last live
  prefix page so Pallas elides their DMA.
- last grid step: the span attends its OWN fresh k/v straight from
  VMEM scratch (causal within the span — row ``j`` sees span columns
  ``<= j``), the softmax finalizes, and the O-proj runs as a
  head-blocked split-K matmul against the resident ``w_o`` with the
  residual added in place.  Span column 0 is visible to every row, so
  even dead rows (``j >= lens[b]``) normalize over a finite score and
  emit bounded garbage the caller discards — slot-0-style inertness.

GQA layout: within one kv head the q rows form a ``(G*C, D)`` tile with
row ``gq * C + j`` (group-major), so each group's span rows are a
CONTIGUOUS C-row block — the grouped layout is assembled from the
``(C, Nq)`` projection by static row-block copies, no in-kernel
transposes.

``supported()`` gates on fp dtypes (unquantized projections), 128-
aligned widths, the ragged kernel's page-size rules, pool dtype ==
activation dtype (the span attends scratch values rounded exactly like
the pool write), and the resident-VMEM footprint.  Everything the gate
declines — int8 KV pools, quantized weights, LoRA, meshes, 7B-class
VMEM overflow — falls back to the XLA composition in
``incubate.nn.functional.mega_decode_layer``, which is the pinned
numerical contract (tests/test_mega_decode.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp
from .. import tuning
from ._common import VMEM_BUDGET, VMEM_LIMIT
from ._common import mxu_precision as _precision
from .fused_norm_qkv import _rot_selector, _tile_selector

NEG_INF = -1e30


def _kernel(tables_ref, starts_ref, lens_ref,            # scalar prefetch
            x_ref, g_ref, wq_ref, wk_ref, wv_ref, wo_ref,
            cos_ref, sin_ref, rq_ref, rk_ref, tq_ref, tk_ref,
            k_ref, v_ref,                                # pool page blocks
            o_ref, ko_ref, vo_ref,                       # out blocks
            q_scr, k_scr, v_scr, m_scr, l_scr, acc_scr,  # VMEM scratch
            *, page, scale, pages_per_seq, h_kv, g, c, hd, eps):
    b = pl.program_id(0)
    ip = pl.program_id(1)
    rows = g * c
    prec = _precision(x_ref.dtype)

    @pl.when(ip == 0)
    def _pre_attention():
        # stages 1-3: rms-norm → qkv projections → selector-matmul rope,
        # one read of the x tile, everything VMEM-resident after
        x = x_ref[0].astype(jnp.float32)                     # (C, H)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        nx = (x * jax.lax.rsqrt(ms + eps)
              * g_ref[...].astype(jnp.float32)).astype(x_ref.dtype)

        def proj(w_ref):
            return jax.lax.dot(nx, w_ref[...], precision=prec,
                               preferred_element_type=jnp.float32)

        def rope(y, r_ref, t_ref):
            # identical arithmetic to fused_norm_qkv._kernel: the
            # projection rounds to x.dtype FIRST (mirroring the unfused
            # path); each {0,±1}/{0,1} selector is stored in its matmul
            # partner's dtype and the precision follows the operands
            yb = y.astype(x_ref.dtype)
            cprec = _precision(cos_ref.dtype)
            cos = jax.lax.dot(cos_ref[0], t_ref[...], precision=cprec,
                              preferred_element_type=jnp.float32)
            sin = jax.lax.dot(sin_ref[0], t_ref[...], precision=cprec,
                              preferred_element_type=jnp.float32)
            rot = jax.lax.dot(yb, r_ref[...], precision=prec,
                              preferred_element_type=jnp.float32)
            return yb.astype(jnp.float32) * cos + rot * sin

        qb = rope(proj(wq_ref), rq_ref, tq_ref).astype(x_ref.dtype)
        kb = rope(proj(wk_ref), rk_ref, tk_ref).astype(x_ref.dtype)
        vb = proj(wv_ref).astype(x_ref.dtype)
        # span k/v leave as outputs for the caller's pool scatter; the
        # scratch copies (same x.dtype rounding as the pool write) are
        # what the span stage attends, so kernel and composition see
        # identical span bytes
        k_scr[...] = kb
        v_scr[...] = vb
        ko_ref[0] = kb
        vo_ref[0] = vb
        # grouped-GQA q layout: kv head hk owns rows
        # [hk*G*C, (hk+1)*G*C) with row gq*C + j — each (gq, head)
        # column block of the (C, Nq) projection lands as one
        # contiguous C-row copy (no transposes)
        for hk in range(h_kv):
            for gq in range(g):
                hh = hk * g + gq
                q_scr[hk * rows + gq * c:hk * rows + (gq + 1) * c, :] = \
                    qb[:, hh * hd:(hh + 1) * hd]
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = starts_ref[b]

    def _online_update(hk, s, v):
        """One online-softmax accumulation for kv head ``hk``:
        ``s`` (G*C, S) masked scores, ``v`` (S, D) values."""
        rr = slice(hk * rows, (hk + 1) * rows)
        m_prev = m_scr[rr]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[rr] = l_scr[rr] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[rr] = acc_scr[rr] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        m_scr[rr] = m_new

    @pl.when(ip * page < start)
    def _prefix_pages():
        # stage 4a: the cached prefix, straight from the paged pools.
        # Only positions < start are the prefix — the span's own
        # positions attend from scratch in the span stage, so a page
        # straddling `start` masks its span part off here.
        pos = ip * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        live = pos < start
        for hk in range(h_kv):
            q = q_scr[hk * rows:(hk + 1) * rows].astype(jnp.float32)
            k = k_ref[0, :, hk].astype(jnp.float32)       # (page, D)
            v = v_ref[0, :, hk].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
            _online_update(hk, jnp.where(live, s * scale, NEG_INF), v)

    @pl.when(ip == pages_per_seq - 1)
    def _span_and_finalize():
        # stage 4b: the span's own fresh k/v from VMEM scratch — row j
        # (position start+j) sees span columns j' <= j.  Column 0 is
        # visible to EVERY row, so dead rows normalize finite garbage.
        j_row = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0) % c
        j_col = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1)
        live = j_col <= j_row
        for hk in range(h_kv):
            q = q_scr[hk * rows:(hk + 1) * rows].astype(jnp.float32)
            k = k_scr[:, hk * hd:(hk + 1) * hd].astype(jnp.float32)
            v = v_scr[:, hk * hd:(hk + 1) * hd].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
            _online_update(hk, jnp.where(live, s * scale, NEG_INF), v)
        # stage 5: finalize + O-proj (head-blocked split-K against the
        # resident w_o) + residual, all before anything leaves VMEM.
        # The attention output rounds to x.dtype per head block exactly
        # where the composition rounds its (B, C, H, D) attend output.
        denom = jnp.maximum(l_scr[...], 1e-30)
        att = acc_scr[...] / denom                        # (Hkv*G*C, D)
        acc_o = jnp.zeros((c, o_ref.shape[-1]), jnp.float32)
        for hk in range(h_kv):
            for gq in range(g):
                hh = hk * g + gq
                blk = att[hk * rows + gq * c:hk * rows + (gq + 1) * c, :]
                blk = blk.astype(x_ref.dtype)
                acc_o = acc_o + jax.lax.dot(
                    blk, wo_ref[hh * hd:(hh + 1) * hd, :], precision=prec,
                    preferred_element_type=jnp.float32)
        o_ref[0] = x_ref[0] + acc_o.astype(x_ref.dtype)


def mega_decode(x, norm_weight, w_q, w_k, w_v, w_o, cos, sin,
                k_pool, v_pool, block_tables, starts, lens,
                head_dim: int, eps: float = 1e-5, scale=None,
                interpret: bool = False):
    """One decoder layer's ragged attention block in one dispatch.

    x: (B, C, H) residual-stream span batch (UN-normed); norm_weight:
    (H,); w_q: (H, Nq); w_k/w_v: (H, Nk); w_o: (Nq, H); cos/sin:
    (B, C, head_dim) per-slot rope tables; pools (NB, page, H_kv, D);
    tables (B, MB) int32; starts/lens (B,) int32.

    Returns ``(out (B, C, H) = x + o_proj(attend), span_k (B, C, Nk),
    span_v (B, C, Nk))`` — the caller scatters span_k/span_v into the
    pools via ``_paged_span_write`` (the pool update stays byte-
    identical to the composition's, OOB dead-slot drop included).

    ``interpret=True`` runs in the Pallas interpreter (CPU CI).
    """
    b, c, h = x.shape
    nq = w_q.shape[1]
    nk = w_k.shape[1]
    nb, page, h_kv, d = k_pool.shape
    mb = block_tables.shape[1]
    g = (nq // head_dim) // h_kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    rq = jnp.asarray(_rot_selector(nq, head_dim), x.dtype)
    rk = jnp.asarray(_rot_selector(nk, head_dim), x.dtype)
    tq = jnp.asarray(_tile_selector(head_dim, nq), cos.dtype)
    tk = jnp.asarray(_tile_selector(head_dim, nk), cos.dtype)

    grid = (b, mb)

    def bmap(ib, ip, tables, starts_, lens_):
        return (ib, 0, 0)

    def wmap(ib, ip, tables, starts_, lens_):
        return (0, 0)

    def kv_map(ib, ip, tables, starts_, lens_):
        # Clamp skipped pages (at/past the prefix's end) to the last
        # prefix page: Pallas elides the re-fetch of a resident block,
        # so decode-dominated batches do prefix-sized DMA work — and
        # padding/OOB table entries never dereference into the pool.
        last_pref = jnp.maximum(starts_[ib] - 1, 0) // page
        idx = tables[ib, jnp.minimum(ip, last_pref)]
        return (jnp.clip(idx, 0, nb - 1), 0, 0, 0)

    kernel = functools.partial(
        _kernel, page=page, scale=float(scale), pages_per_seq=mb,
        h_kv=h_kv, g=g, c=c, hd=head_dim, eps=float(eps))
    out, k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, c, h), bmap),            # x
                pl.BlockSpec((1, h), wmap),               # norm weight
                pl.BlockSpec((h, nq), wmap),              # wq
                pl.BlockSpec((h, nk), wmap),              # wk
                pl.BlockSpec((h, nk), wmap),              # wv
                pl.BlockSpec((nq, h), wmap),              # wo
                pl.BlockSpec((1, c, head_dim), bmap),     # cos
                pl.BlockSpec((1, c, head_dim), bmap),     # sin
                pl.BlockSpec((nq, nq), wmap),             # R_q
                pl.BlockSpec((nk, nk), wmap),             # R_k
                pl.BlockSpec((head_dim, nq), wmap),       # T_q
                pl.BlockSpec((head_dim, nk), wmap),       # T_k
                pl.BlockSpec((1, page, h_kv, d), kv_map),
                pl.BlockSpec((1, page, h_kv, d), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, c, h), bmap),
                pl.BlockSpec((1, c, nk), bmap),
                pl.BlockSpec((1, c, nk), bmap),
            ],
            scratch_shapes=[
                pltpu.VMEM((h_kv * g * c, head_dim), x.dtype),  # q
                pltpu.VMEM((c, nk), x.dtype),                   # span k
                pltpu.VMEM((c, nk), x.dtype),                   # span v
                pltpu.VMEM((h_kv * g * c, 1), jnp.float32),     # m
                pltpu.VMEM((h_kv * g * c, 1), jnp.float32),     # l
                pltpu.VMEM((h_kv * g * c, head_dim), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, c, h), x.dtype),
            jax.ShapeDtypeStruct((b, c, nk), x.dtype),
            jax.ShapeDtypeStruct((b, c, nk), x.dtype),
        ],
        compiler_params=_pcp()(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="mega_decode",
    )(block_tables, starts, lens, x, norm_weight.reshape(1, h),
      w_q, w_k, w_v, w_o, cos, sin, rq, rk, tq, tk, k_pool, v_pool)
    return out, k_out, v_out


def _resident_bytes(c, h, nq, nk, head_dim, page, h_kv, itemsize):
    """Everything the kernel keeps in scoped VMEM at once: the five
    weight-side operands, the four rope selectors (T at 4 bytes — the
    rope tables may arrive in f32), the double-buffered x/cos/sin/out
    tiles and pool page blocks, the scratch state, and the f32
    temporaries of the pre-attention stage (as fused_norm_qkv)."""
    g = (nq // head_dim) // h_kv
    weights = (h * (nq + 2 * nk) + nq * h) * itemsize
    selectors = (nq * nq + nk * nk) * itemsize \
        + head_dim * (nq + nk) * 4
    tiles = 2 * ((2 * c * h + 2 * c * nk) * itemsize
                 + 2 * c * head_dim * 4)
    pages = 4 * page * h_kv * head_dim * itemsize
    scratch = (h_kv * g * c * head_dim + 2 * c * nk) * itemsize \
        + h_kv * g * c * (head_dim + 2) * 4
    temps = 6 * c * max(h, nq) * 4
    return weights + selectors + tiles + pages + scratch + temps


def supported(x, w_q, w_k, w_o, head_dim: int, cache=None) -> bool:
    """Megakernel gate: fp span batches over fp pools only — 128-aligned
    widths and head_dim (the MXU tiles), the ragged kernel's page-size
    rules, 8-aligned span rows, pool dtype matching the activations
    (the span attends scratch bytes rounded exactly like the pool
    write), and the whole resident set within the VMEM budget.  Int8 KV
    pools, quantized/LoRA projections, meshes and 7B-class widths all
    decline here and take the XLA composition."""
    if x.ndim != 3 or w_q.ndim != 2 or w_k.ndim != 2 or w_o.ndim != 2:
        return False
    b, c, h = x.shape
    nq, nk = w_q.shape[1], w_k.shape[1]
    if h % 128 or nq % 128 or nk % 128 or head_dim % 128:
        return False
    if nq % head_dim or nk % head_dim:
        return False
    h_kv = nk // head_dim
    if (nq // head_dim) % h_kv or c % 8:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    page = 16
    if cache is not None:
        if len(cache) != 2:
            return False        # int8 pools: composition's gather+dequant
        if cache[0].dtype != x.dtype:
            return False
        page = cache[0].shape[1]
    if not (page == 16 or page % 64 == 0):
        return False
    if _resident_bytes(c, h, nq, nk, head_dim, page, h_kv,
                       x.dtype.itemsize) > VMEM_BUDGET:
        return False
    return jax.default_backend() == "tpu"
