"""Fused RMSNorm → QKV projection → RoPE for TPU in Pallas — ONE HBM
read of the hidden states feeding attention.

Why a kernel: the unfused path streams the normed hidden states from HBM
three times (q/k/v projections), then runs rope as a fourth elementwise
pass over q and k.  Step attribution (docs/BENCH.md §attribution) showed
these memory-bound pre-attention passes are where the llama-350m vs
hd128 MFU gap lives.  Here one kernel reads each x tile once, norms it
in VMEM, runs the three projections against resident weights, and
applies rope to q/k before they ever leave VMEM.

TPU-native formulation — no layout ops anywhere:

- rms-norm is a rowwise f32 reduce + rsqrt on the x tile (VPU);
- rope's rotate-half is a matmul against a block-diagonal {0, ±1}
  selector R (one per q/k width, host-built once per geometry) — the
  same trick ``nn.functional._rotate_half_mm`` uses at the XLA level
  (layout-traffic-free, exact in bf16), lifted into the kernel;
- the per-position cos/sin (T, head_dim) are broadcast across heads by a
  second {0, 1} selector matmul (head_dim, width) instead of a lane
  concat, which Mosaic may not support at sub-128 head dims;
- grid = (token-tiles,): all five weight-side operands stay resident in
  VMEM across the grid (their BlockSpec index is constant), so HBM
  traffic is exactly one read of x + one write of q/k/v per step.

``supported()`` gates on the resident-VMEM footprint — 7B-class widths
fall back to the XLA composition (incubate.nn.functional), which under
GSPMD also remains the multi-chip path (Mosaic kernels cannot be
auto-partitioned).  Block shapes come from tools/tuned_configs.json
(ops.tuning, trace time); sweep with ``python tools/autotune.py``.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.compat import pallas_compiler_params as _pcp
from .. import tuning
from ._common import VMEM_BUDGET, VMEM_LIMIT
from ._common import mxu_precision as _precision

DEFAULT_BLOCK_T = 256


@functools.lru_cache(maxsize=8)
def _rot_selector(width: int, head_dim: int):
    """(width, width) block-diagonal rotate-half selector R:
    ``(y @ R)[j] = -y[j + hd/2]`` for the first half of each head,
    ``+y[j - hd/2]`` for the second — np-built once per geometry."""
    half = head_dim // 2
    r = np.zeros((width, width), np.float32)
    for h0 in range(0, width, head_dim):
        r[h0 + half:h0 + head_dim, h0:h0 + half] = -np.eye(half)
        r[h0:h0 + half, h0 + half:h0 + head_dim] = np.eye(half)
    return r


@functools.lru_cache(maxsize=8)
def _tile_selector(head_dim: int, width: int):
    """(head_dim, width) selector T with ``T[d, h*hd + d] = 1`` — one
    matmul broadcasts (bt, head_dim) cos/sin to every head's columns."""
    t = np.zeros((head_dim, width), np.float32)
    for h0 in range(0, width, head_dim):
        t[:, h0:h0 + head_dim] = np.eye(head_dim)
    return t


def _kernel(x_ref, g_ref, wq_ref, wk_ref, wv_ref, cos_ref, sin_ref,
            rq_ref, rk_ref, tq_ref, tk_ref,
            q_ref, k_ref, v_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    nx = (x * jax.lax.rsqrt(ms + eps)
          * g_ref[...].astype(jnp.float32)).astype(x_ref.dtype)
    prec = _precision(x_ref.dtype)

    def proj(w_ref):
        return jax.lax.dot(nx, w_ref[...], precision=prec,
                           preferred_element_type=jnp.float32)

    def rope(y, r_ref, t_ref):
        # cos/sin tiled across heads and the rotation — all MXU passes
        # against {0, ±1} selectors (one non-zero per column, so exact
        # in any dtype), accumulation in f32.  Mosaic wants both
        # operands of a matmul in ONE dtype and refuses an fp32 contract
        # precision on bf16 operands, so each selector is stored in its
        # partner's dtype (R in x.dtype, T in the cos/sin dtype) and the
        # precision request follows the operands.  The projection is
        # rounded to x.dtype FIRST, mirroring the unfused path (rope
        # there runs on the projection layer's output dtype).
        yb = y.astype(x_ref.dtype)
        cprec = _precision(cos_ref.dtype)
        cos = jax.lax.dot(cos_ref[...], t_ref[...], precision=cprec,
                          preferred_element_type=jnp.float32)
        sin = jax.lax.dot(sin_ref[...], t_ref[...], precision=cprec,
                          preferred_element_type=jnp.float32)
        rot = jax.lax.dot(yb, r_ref[...], precision=prec,
                          preferred_element_type=jnp.float32)
        return yb.astype(jnp.float32) * cos + rot * sin

    q = proj(wq_ref)
    k = proj(wk_ref)
    q_ref[...] = rope(q, rq_ref, tq_ref).astype(q_ref.dtype)
    k_ref[...] = rope(k, rk_ref, tk_ref).astype(k_ref.dtype)
    v_ref[...] = proj(wv_ref).astype(v_ref.dtype)


def _block_t(t, h, nq, nk, head_dim, block_t=None):
    """Token-tile rows: explicit arg, then tuned configs (trace time,
    ops.tuning), then the default — sublane-aligned, at most T."""
    if block_t is None:
        cfg = tuning.tuned_config(
            "fused_rms_rope_qkv",
            tuning.geom_key(h=h, nq=nq, nk=nk, hd=head_dim))
        block_t = cfg.get("block_t", DEFAULT_BLOCK_T)
    bt = max(8, int(block_t) // 8 * 8)
    return min(bt, -(-t // 8) * 8)


def _vmem_estimate(bt, h, nq, nk, head_dim, itemsize):
    """Scoped VMEM Mosaic allocates: the weight-side operands (one
    buffer each — their block never changes), the double-buffered x /
    cos / sin / q / k / v tiles, and the f32 temporaries of one tile
    (x, projection, tiled cos and sin, rotation, product).  The T
    selectors and cos/sin tiles are counted at 4 bytes: the rope tables
    may arrive in f32 whatever the activation dtype."""
    resident = ((h * (nq + 2 * nk) + nq * nq + nk * nk + h) * itemsize
                + head_dim * (nq + nk) * 4)
    pipelined = 2 * (bt * (h + nq + 2 * nk) * itemsize
                     + 2 * bt * head_dim * 4)
    temps = 6 * bt * max(h, nq) * 4
    return resident + pipelined + temps


def fused_rms_rope_qkv(x, norm_weight, w_q, w_k, w_v, cos, sin,
                       head_dim: int, eps: float = 1e-5,
                       block_t=None, interpret: bool = False):
    """rms_norm(x) projected to q/k/v with rotate-half rope applied to
    q and k, in one kernel.

    x: (T, H) hidden states (batch*seq flattened); norm_weight: (H,);
    w_q: (H, Nq); w_k/w_v: (H, Nk) (GQA: Nk = H_kv·head_dim ≤ Nq);
    cos/sin: (T, head_dim) per-token rope tables.  Returns
    ``(q (T, Nq), k (T, Nk), v (T, Nk))`` in ``x.dtype``.
    """
    t, h = x.shape
    nq = w_q.shape[1]
    nk = w_k.shape[1]
    bt = _block_t(t, h, nq, nk, head_dim, block_t)
    rem = t % bt
    xp = jnp.pad(x, ((0, bt - rem), (0, 0))) if rem else x
    cosp = jnp.pad(cos, ((0, bt - rem), (0, 0))) if rem else cos
    sinp = jnp.pad(sin, ((0, bt - rem), (0, 0))) if rem else sin
    tp = xp.shape[0]

    rq = jnp.asarray(_rot_selector(nq, head_dim), x.dtype)
    rk = jnp.asarray(_rot_selector(nk, head_dim), x.dtype)
    tq = jnp.asarray(_tile_selector(head_dim, nq), cos.dtype)
    tk = jnp.asarray(_tile_selector(head_dim, nk), cos.dtype)

    def tmap(it):
        return (it, 0)

    def wmap(it):
        return (0, 0)

    q, k, v = pl.pallas_call(
        functools.partial(_kernel, eps=float(eps)),
        grid=(tp // bt,),
        in_specs=[
            pl.BlockSpec((bt, h), tmap),          # x
            pl.BlockSpec((1, h), wmap),           # norm weight
            pl.BlockSpec((h, nq), wmap),          # wq
            pl.BlockSpec((h, nk), wmap),          # wk
            pl.BlockSpec((h, nk), wmap),          # wv
            pl.BlockSpec((bt, head_dim), tmap),   # cos
            pl.BlockSpec((bt, head_dim), tmap),   # sin
            pl.BlockSpec((nq, nq), wmap),         # R_q
            pl.BlockSpec((nk, nk), wmap),         # R_k
            pl.BlockSpec((head_dim, nq), wmap),   # T_q
            pl.BlockSpec((head_dim, nk), wmap),   # T_k
        ],
        out_specs=[
            pl.BlockSpec((bt, nq), tmap),
            pl.BlockSpec((bt, nk), tmap),
            pl.BlockSpec((bt, nk), tmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, nq), x.dtype),
            jax.ShapeDtypeStruct((tp, nk), x.dtype),
            jax.ShapeDtypeStruct((tp, nk), x.dtype),
        ],
        compiler_params=_pcp()(dimension_semantics=("parallel",),
                               vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="fused_rms_rope_qkv",
    )(xp, norm_weight.reshape(1, h), w_q, w_k, w_v, cosp, sinp,
      rq, rk, tq, tk)
    return q[:t], k[:t], v[:t]


def supported(x, w_q, w_k, head_dim: int) -> bool:
    """Mosaic-shape gate: 128-aligned widths, even head_dim, fp dtypes,
    and the allocation at the block the kernel will use (resident
    weight-side operands, tiles, temporaries) within the VMEM budget."""
    if x.ndim != 2 or w_q.ndim != 2 or w_k.ndim != 2:
        return False
    h = x.shape[1]
    nq, nk = w_q.shape[1], w_k.shape[1]
    if h % 128 or nq % 128 or nk % 128 or head_dim % 2:
        return False
    if nq % head_dim or nk % head_dim:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    bt = _block_t(x.shape[0], h, nq, nk, head_dim)
    return _vmem_estimate(bt, h, nq, nk, head_dim,
                          x.dtype.itemsize) <= VMEM_BUDGET
