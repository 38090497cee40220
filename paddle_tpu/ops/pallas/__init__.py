"""Pallas TPU kernel pack (reference: paddle/phi/kernels/fusion/gpu/).

Registers kernels into the ops.dispatch registry; callers always have an
XLA fallback so CPU tests remain authoritative for numerics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import compat as _compat
from .. import dispatch
from . import flash_attention as _fa


def _xla_fallback(q, k, v, causal, scale):
    from ...nn import functional as F
    return F._xla_attention(q, k, v, is_causal=causal, scale=scale)


def _active_mesh():
    """The physical mesh entered via ``with mesh:`` (TrainStep does this
    around trace/lower), or None."""
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return None if (mesh.empty or mesh.size == 1) else mesh


def _flash_shard_spec(mesh, q, k):
    """PartitionSpec keeping the kernel per-device on a hybrid mesh: batch
    over the data axes, heads over mp, seq/head_dim replicated.  Mosaic
    kernels cannot be auto-partitioned by GSPMD — without an explicit
    shard_map the multi-chip lowering fails outright.  Returns None when
    the kernel cannot be cleanly partitioned (caller falls back to XLA)."""
    import math as _math

    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names
    if "sep" in names and mesh.shape["sep"] > 1:
        return None  # sequence parallel: the ring-attention path owns this
    batch_axes = tuple(a for a in ("dp", "sharding")
                       if a in names and mesh.shape[a] > 1)
    mp = "mp" if "mp" in names and mesh.shape["mp"] > 1 else None
    bdeg = _math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    mdeg = mesh.shape[mp] if mp else 1
    b, _, h, _ = q.shape
    hk = k.shape[2]
    if b % bdeg or h % mdeg or hk % mdeg:
        return None
    return P(batch_axes if batch_axes else None, None, mp, None)


def _flash_attention_dispatch(q, k, v, causal=False, scale=None):
    if not _fa.supported(q, k, v, causal=causal):
        return _xla_fallback(q, k, v, causal, scale)
    mesh = _active_mesh()
    if mesh is None:
        return _fa.flash_attention(q, k, v, causal=causal, scale=scale)
    spec = _flash_shard_spec(mesh, q, k)
    if spec is None:
        return _xla_fallback(q, k, v, causal, scale)
    fn = _compat.shard_map(
        lambda q_, k_, v_: _fa.flash_attention(q_, k_, v_, causal=causal,
                                               scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call's out_shape carries no varying-mesh-axes annotation
        check_vma=False)
    return fn(q, k, v)


dispatch.register("flash_attention", _flash_attention_dispatch, platform="tpu")

from . import decode_attention as _da


def _paged_attention_dispatch(q, k_pool, v_pool, block_tables, lens,
                              scale=None):
    if not _da.supported(q, k_pool, v_pool, block_tables, lens):
        return None  # caller falls back to the XLA gather formulation
    return _da.paged_attention(q, k_pool, v_pool, block_tables, lens,
                               scale=scale)


dispatch.register("paged_attention", _paged_attention_dispatch,
                  platform="tpu")

from . import ragged_attention as _ra


def _ragged_paged_attention_dispatch(q, k_pool, v_pool, block_tables,
                                     starts, lens, scale=None):
    if not _ra.supported(q, k_pool, v_pool, block_tables, starts, lens):
        return None  # caller falls back to the XLA gather formulation
    return _ra.ragged_paged_attention(q, k_pool, v_pool, block_tables,
                                      starts, lens, scale=scale)


dispatch.register("ragged_paged_attention", _ragged_paged_attention_dispatch,
                  platform="tpu")


def _eva_ragged_paged_attention_dispatch(q, k_pool, v_pool, block_tables,
                                         starts, lens, skips, scale=None):
    """EVA's attention (``incubate.nn.functional.eva_paged_attend``): the
    ragged kernel over a table of summary pages then window pages, under
    a name of its own."""
    if not _ra.supported(q, k_pool, v_pool, block_tables, starts, lens):
        return None  # caller falls back to the XLA gather formulation
    return _ra.ragged_paged_attention(q, k_pool, v_pool, block_tables,
                                      starts, lens, scale=scale, skips=skips,
                                      name="eva_ragged_paged_attention")


dispatch.register("eva_ragged_paged_attention",
                  _eva_ragged_paged_attention_dispatch, platform="tpu")

# -- fused-kernel library (docs/KERNELS.md) ---------------------------------
# Each dispatch returns None when the kernel cannot serve (shape gate or
# an active mesh — GSPMD cannot auto-partition Mosaic kernels) and the
# caller falls back to the XLA composition in incubate.nn.functional.

from . import fused_mlp as _fm
from . import fused_norm_qkv as _fq
from . import fused_adamw as _fadamw


def _fused_swiglu_dispatch(x, w_gate, w_up, w_down, live=None,
                           interpret=False):
    """``live`` is the serving step's ``(order, inverse, n_live)``
    (``incubate.nn.functional.live_token_order``): where the kernel takes
    its weight-stationary order, the live rows are gathered to the front
    for it and every lane is put back after (two ``(T, H)`` moves)."""
    if _active_mesh() is not None or not _fm.supported(x, w_gate, w_down):
        return None
    if live is not None and _fm.holds_live(x, w_gate):
        order, inverse, n_live = live
        return _fm.fused_swiglu_mlp(x[order], w_gate, w_up, w_down, n_live,
                                    interpret=interpret)[inverse]
    return _fm.fused_swiglu_mlp(x, w_gate, w_up, w_down,
                                interpret=interpret)


dispatch.register("fused_swiglu_mlp", _fused_swiglu_dispatch,
                  platform="tpu")


def _fused_rms_rope_qkv_dispatch(x, norm_weight, w_q, w_k, w_v, cos, sin,
                                 head_dim, eps):
    if _active_mesh() is not None \
            or not _fq.supported(x, w_q, w_k, head_dim):
        return None
    return _fq.fused_rms_rope_qkv(x, norm_weight, w_q, w_k, w_v, cos,
                                  sin, head_dim, eps=eps)


dispatch.register("fused_rms_rope_qkv", _fused_rms_rope_qkv_dispatch,
                  platform="tpu")


def _fused_adamw_dispatch(p, g, m, v, lr, c1, c2, *, beta1, beta2, eps,
                          wd, low_dtype=None):
    if _active_mesh() is not None \
            or not _fadamw.eligible(p, g, low_dtype):
        return None
    return _fadamw.fused_adamw_update(p, g, m, v, lr, c1, c2,
                                      beta1=beta1, beta2=beta2, eps=eps,
                                      wd=wd, low_dtype=low_dtype)


dispatch.register("fused_adamw", _fused_adamw_dispatch, platform="tpu")

from . import gated_delta as _gd


def _gated_delta_rule_dispatch(q, k, v, g, beta, chunk, interpret=False):
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if _active_mesh() is not None \
            or not _gd.supported(q, k, v, g, beta, chunk):
        return None
    return _gd.gated_delta_rule(q, k, v, g, beta, interpret)


dispatch.register("gated_delta_rule", _gated_delta_rule_dispatch,
                  platform="tpu")

from . import lora_matmul as _lora


def _lora_bgmv_dispatch(x, a, b, idx):
    # GSPMD cannot auto-partition Mosaic kernels: a meshed (TP) engine
    # takes the XLA gather+einsum composition, which partitions fine
    # (the stacks are small and replicated)
    if _active_mesh() is not None or not _lora.supported(x, a, b):
        return None
    return _lora.grouped_bgmv(x, a, b, idx)


dispatch.register("lora_bgmv", _lora_bgmv_dispatch, platform="tpu")
