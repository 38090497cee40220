"""Flash attention for TPU in Pallas (fwd + bwd).

Reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu (FA2
wrapper).  This is NOT a port — it is the TPU-native online-softmax
algorithm laid out for MXU/VMEM:

- grid over (batch, q-head, q-block, kv-block); the innermost grid dim is
  sequential on TPU, so the running max/denominator/accumulator live in
  VMEM scratch across kv-blocks (no HBM round-trips);
- causal blocks past the diagonal are skipped via ``pl.when`` predication;
- GQA folds the kv-head mapping into the BlockSpec index maps (no repeated
  kv materialisation);
- backward = two kernels (dk/dv with kv-major grid, dq with q-major grid),
  both recomputing p = exp(qk - L) from the saved per-row logsumexp L,
  exactly the flash-attention-2 recipe.

Layout [batch, seq, heads, head_dim] (the reference's flash layout).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp

import os

# Block sizes are tunable per hardware generation via PDTPU_FLASH_BLOCK_Q/K.
# Defaults from the v5e on-chip sweep (2026-07-30, llama-350m train step):
# (1024,1024) 0.433 MFU > (512,1024) 0.422 > (512,2048) 0.414 > others;
# (1024,2048) exceeds VMEM.
DEFAULT_BLOCK_Q = int(os.environ.get("PDTPU_FLASH_BLOCK_Q", 1024))
DEFAULT_BLOCK_K = int(os.environ.get("PDTPU_FLASH_BLOCK_K", 1024))
# backward defaults to the forward blocks unless overridden — the bwd
# kernels have different VMEM pressure (5 operands + 2 scratch), so their
# optimum can differ from the fwd's
BWD_BLOCK_Q = int(os.environ.get("PDTPU_FLASH_BWD_BLOCK_Q", 0)) or None
BWD_BLOCK_K = int(os.environ.get("PDTPU_FLASH_BWD_BLOCK_K", 0)) or None
# "merged": one kernel produces dk/dv (VMEM-accumulated) + dq (per-k-block
# partials, reduced outside) — each tile's s/p recompute shared by all
# three grads.  "split": the original dkv + dq kernel pair.
BWD_MODE = os.environ.get("PDTPU_FLASH_BWD_MODE", "merged")
NEG_INF = -1e30
# The softmax runs in the base-2 domain: fold log2(e) into the qk scale so
# the VPU evaluates exp2 directly instead of exp (= exp2 plus a per-element
# multiply). The domain is internal — the saved per-row statistic is
# log2-sum-exp2 and both bwd kernels consume it in the same domain.
LOG2E = math.log2(math.e)
# grid = (batch, head, major-block, minor-block): only the innermost dim
# carries the running-statistics dependency; the rest are parallel
_DIMS = _pcp()(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _pick_block(n, preferred):
    b = min(preferred, n)
    while n % b:
        b //= 2
    return max(b, 1)


def _block_live(iq, ik, block_q, block_k, offset):
    """True when the (iq, ik) tile intersects the causal region (row i
    attends key j iff j <= i + offset; bottom-right aligned)."""
    return iq * block_q + block_q - 1 + offset >= ik * block_k


def _block_fully_visible(iq, ik, block_q, block_k, offset):
    """True when every (row, col) in the tile satisfies the causal
    predicate — the mask (2 iotas + compare + select per element) can be
    skipped entirely. For square blocks this is every tile strictly below
    the diagonal, i.e. most of the live tiles at long seq."""
    return iq * block_q + offset >= ik * block_k + block_k - 1


def _causal_mask(s, iq, ik, block_q, block_k, offset):
    """Apply the bottom-right-aligned causal mask to a score tile."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + iq * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ik * block_k
    return jnp.where(rows + offset >= cols, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, block_q, block_k, offset):
    # ``offset`` = sk - sq: causal masking is bottom-right aligned (row i
    # attends key j iff j <= i + offset), matching the XLA fallback
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _body(masked):
        q = q_ref[0, 0]                              # (bq, d), input dtype
        k = k_ref[0, 0]                              # (bk, d)
        v = v_ref[0, 0]                              # (bk, d)
        # MXU runs at full rate on the input dtype (bf16) with f32 accumulate;
        # scores land in the base-2 domain (scale carries log2(e))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * (
                                    scale * LOG2E)
        if masked:
            s = _causal_mask(s, iq, ik, block_q, block_k, offset)
        m_prev = m_scr[:, 0]                          # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_cur[:, None])
        alpha = jnp.exp2(m_prev - m_cur)
        l_cur = alpha * l_scr[:, 0] + jnp.sum(p, axis=1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_cur
        l_scr[:, 0] = l_cur

    if not causal:
        _body(False)
    else:
        # grid-step predication: interior (fully visible) tiles skip the
        # mask's iota/compare/select VPU work entirely
        live = _block_live(iq, ik, block_q, block_k, offset)
        full = _block_fully_visible(iq, ik, block_q, block_k, offset)
        pl.when(live & full)(lambda: _body(False))
        pl.when(live & jnp.logical_not(full))(lambda: _body(True))

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)
        # per-row log2-sum-exp2 (base-2 domain), saved for backward
        l_ref[0, 0] = (m_scr[:] + jnp.log2(safe_l)[:, None]).astype(jnp.float32)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    # head-major layout for clean 2-D blocks
    qt = q.transpose(0, 2, 1, 3)          # (b, h, sq, d)
    kt = k.transpose(0, 2, 1, 3)          # (b, hkv, sk, d)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, h, sq // bq, sk // bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, offset=sk - sq)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        compiler_params=_DIMS,
        name="flash_attention_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]  # (b,s,h,d), (b,h,s)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, causal, block_q, block_k, offset):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _body(masked):
        q = q_ref[0, 0]                               # (bq, d)
        k = k_ref[0, 0]                               # (bk, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]                             # (bq, d)
        lse = lse_ref[0, 0][:, 0]                     # (bq,)
        delta = delta_ref[0, 0][:, 0]                 # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * (
                                    scale * LOG2E)
        if masked:
            s = _causal_mask(s, iq, ik, block_q, block_k, offset)
        p = jnp.exp2(s - lse[:, None])                # (bq, bk) f32
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if not causal:
        _body(False)
    else:
        live = _block_live(iq, ik, block_q, block_k, offset)
        full = _block_fully_visible(iq, ik, block_q, block_k, offset)
        pl.when(live & full)(lambda: _body(False))
        pl.when(live & jnp.logical_not(full))(lambda: _body(True))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_merged_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr, *,
                       scale, causal, block_q, block_k, offset):
    """One-pass backward: dk/dv accumulate in VMEM over the inner q-blocks
    (kv-major grid, as in _bwd_dkv_kernel) and the per-tile dq
    contribution ds @ k is written to a per-k-block partial (unique
    (ik, iq) slot — no cross-step accumulation), reduced outside.  Halves
    the s/p recompute vs the split dkv+dq pair: each tile's qk product and
    exp2 are computed once and feed all three gradients."""
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _body(masked):
        q = q_ref[0, 0]                               # (bq, d)
        k = k_ref[0, 0]                               # (bk, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]                             # (bq, d)
        lse = lse_ref[0, 0][:, 0]                     # (bq,)
        delta = delta_ref[0, 0][:, 0]                 # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * (
                                    scale * LOG2E)
        if masked:
            s = _causal_mask(s, iq, ik, block_q, block_k, offset)
        p = jnp.exp2(s - lse[:, None])                # (bq, bk) f32
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dqp_ref[0, 0, 0] = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if not causal:
        _body(False)
    else:
        live = _block_live(iq, ik, block_q, block_k, offset)
        full = _block_fully_visible(iq, ik, block_q, block_k, offset)
        pl.when(live & full)(lambda: _body(False))
        pl.when(live & jnp.logical_not(full))(lambda: _body(True))
        # dead tiles still own a unique dq-partial slot: zero it
        pl.when(jnp.logical_not(live))(
            lambda: dqp_ref.__setitem__((0, 0, 0),
                                        jnp.zeros_like(dqp_ref[0, 0, 0])))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, block_q, block_k, offset):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0]
        delta = delta_ref[0, 0][:, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * (
                                    scale * LOG2E)
        if masked:
            s = _causal_mask(s, iq, ik, block_q, block_k, offset)
        p = jnp.exp2(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if not causal:
        _body(False)
    else:
        live = _block_live(iq, ik, block_q, block_k, offset)
        full = _block_fully_visible(iq, ik, block_q, block_k, offset)
        pl.when(live & full)(lambda: _body(False))
        pl.when(live & jnp.logical_not(full))(lambda: _body(True))

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_vmem_estimate(bq, bk, d, itemsize, merged):
    """Rough per-core VMEM bytes for one bwd grid cell: operand blocks
    (q, k, v, do), f32 score/ds tiles, accumulator scratch, and (merged)
    the dq-partial output block.  Used to auto-shrink blocks below the
    ~16 MiB scoped-vmem limit instead of failing at compile time."""
    operands = (2 * bq * d + 2 * bk * d) * itemsize
    tiles = 3 * bq * bk * 4            # s/p, dp, ds in f32
    scratch = 2 * bk * d * 4 + 2 * bk * d * 4   # dk/dv scratch + out blocks
    if merged:
        scratch += bq * d * 4          # dq-partial output block
    # calibrated against the compiler's accounting: a d128 f32 merged cell
    # at 1024/1024 measures 16.32M (estimate 17.3M); a d64 bf16 cell
    # estimates 14.4M and compiles at 1024 blocks
    return operands + tiles + scratch


def _flash_bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k,
               dlse=None):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    bq = _pick_block(sq, BWD_BLOCK_Q or block_q)
    bk = _pick_block(sk, BWD_BLOCK_K or block_k)
    # VMEM auto-shrink — per dimension: an explicit PDTPU_FLASH_BWD_BLOCK_*
    # override pins THAT dimension (the operator knows the real budget);
    # the other still shrinks
    lock_q, lock_k = bool(BWD_BLOCK_Q), bool(BWD_BLOCK_K)
    vmem_budget = int(15.5 * 2 ** 20)
    while _bwd_vmem_estimate(bq, bk, d, q.dtype.itemsize,
                             BWD_MODE == "merged") > vmem_budget:
        can_q = not lock_q and bq > 128
        can_k = not lock_k and bk > 128
        if not (can_q or can_k):
            break
        if can_q and (bq >= bk or not can_k):
            bq //= 2
        else:
            bk //= 2
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)
    # delta = rowsum(dO * O), fp32 (cheap XLA op)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)                         # (b, h, sq)
    if dlse is not None:
        # lse cotangent: ∂L/∂z_j += dlse·p_j·log2(e) — folds into the
        # kernels' p∘(dp − delta) form as delta' = delta − dlse·log2(e)
        delta = delta - dlse.astype(jnp.float32) * LOG2E
    lse4 = lse[..., None]                            # (b, h, sq, 1)
    delta4 = delta[..., None]

    mode = BWD_MODE
    if mode == "merged" and sk // bk > 8:
        # the dq-partials buffer is (sk/bk) x the dq footprint in f32 HBM;
        # past ~8 k-blocks (long context) that transient outweighs the
        # saved recompute — fall back to the split pair, which accumulates
        # dq in VMEM scratch
        mode = "split"
    if mode == "merged":
        # one-pass kernel: dq comes out as per-k-block partials (unique
        # (ik, iq) slot each) reduced here; each tile's s/p recompute is
        # shared by all three gradients
        nkb = sk // bk
        kernel_m = functools.partial(_bwd_merged_kernel, scale=scale,
                                     causal=causal, block_q=bq, block_k=bk,
                                     offset=sk - sq)
        dk_h, dv_h, dqp = pl.pallas_call(
            kernel_m,
            grid=(b, h, nkb, sq // bq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0)),
                pl.BlockSpec((1, 1, bq, d),
                             lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, d),
                             lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
                pl.BlockSpec((1, 1, 1, bq, d),
                             lambda ib, ih, ik, iq: (ib, ih, ik, iq, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
                jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
                jax.ShapeDtypeStruct((b, h, nkb, sq, d), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            compiler_params=_DIMS,
            name="flash_attention_bwd",
        )(qt, kt, vt, dot, lse4, delta4)
        dq = dqp.sum(axis=2).astype(q.dtype)
        dk = dk_h.reshape(b, hkv, group, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(b, hkv, group, sk, d).sum(axis=2).astype(v.dtype)
        return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
                dv.transpose(0, 2, 1, 3))

    # dk/dv: kv-major grid; per q-head gradients for k/v then summed over
    # the GQA group outside (simpler than atomics across grid cells)
    kernel_dkv = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                   block_q=bq, block_k=bk, offset=sk - sq)
    dk_h, dv_h = pl.pallas_call(
        kernel_dkv,
        grid=(b, h, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_DIMS,
        name="flash_attention_bwd_dkv",
    )(qt, kt, vt, dot, lse4, delta4)

    kernel_dq = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  block_q=bq, block_k=bk, offset=sk - sq)
    dq = pl.pallas_call(
        kernel_dq,
        grid=(b, h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_DIMS,
        name="flash_attention_bwd_dq",
    )(qt, kt, vt, dot, lse4, delta4)

    # fold GQA group: sum per-q-head dk/dv into kv heads
    dk = dk_h.reshape(b, hkv, group, sk, d).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(b, hkv, group, sk, d).sum(axis=2).astype(v.dtype)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, scale, causal, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_attention_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, scale, causal,
                            block_q, block_k)
    return dq, dk, dv


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_lse(q, k, v, scale, causal, block_q, block_k):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k)


def _flash_attention_lse_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return (out, lse), (q, k, v, out, lse)


def _flash_attention_lse_bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, scale, causal,
                            block_q, block_k, dlse=dlse)
    return dq, dk, dv


_flash_attention_lse.defvjp(_flash_attention_lse_fwd,
                            _flash_attention_lse_bwd)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=DEFAULT_BLOCK_Q,
                             block_k=DEFAULT_BLOCK_K):
    """Like :func:`flash_attention` but also returns the per-row
    log2-sum-exp2 statistic ``lse`` (b, h, sq) — the merge currency of
    ring/context-parallel attention.  Differentiable in BOTH outputs: the
    lse cotangent folds into the backward kernels' delta term
    (delta' = delta − dlse·log2(e), from ∂lse2/∂z = p/ln 2)."""
    if causal and q.shape[1] > k.shape[1]:
        raise ValueError(
            f"causal flash attention requires sq <= sk, got sq={q.shape[1]} "
            f"sk={k.shape[1]}: rows with no visible key have undefined "
            "attention (use the XLA fallback)")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_attention_lse(q, k, v, float(scale), bool(causal),
                                int(block_q), int(block_k))


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Public entry: [b, s, h, d] in/out; kv heads may divide q heads (GQA)."""
    if causal and q.shape[1] > k.shape[1]:
        raise ValueError(
            f"causal flash attention requires sq <= sk, got sq={q.shape[1]} "
            f"sk={k.shape[1]}: rows with no visible key have undefined "
            "attention (use the XLA fallback)")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_attention(q, k, v, float(scale), bool(causal),
                            int(block_q), int(block_k))


def supported(q, k, v, causal=False) -> bool:
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return False
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if causal and sq > sk:
        # offset = sk - sq < 0 leaves rows i < -offset with no visible key;
        # the online softmax would silently emit uniform attention for them
        # (and pollute dk/dv) instead of the fallback's NaN — reject.
        return False
    return h % hkv == 0 and d <= 256 and sq >= 8 and sk >= 8
