"""``paddle.incubate.nn.functional`` parity — the fused-op surface.

Reference: python/paddle/incubate/nn/functional/ (fused_rms_norm,
fused_layer_norm, fused_bias_act, fused_dropout_add, fused_linear,
fused_rotary_position_embedding, masked_multihead_attention,
variable_length_memory_efficient_attention) backed by
paddle/phi/kernels/fusion/gpu/ CUDA kernels.

TPU redesign: "fused" is what XLA does by default — these entry points keep
the reference call signatures and lower to jnp compositions XLA fuses into
single kernels (elementwise chains fuse into the preceding matmul/reduce).
The decode-attention ops (masked_multihead_attention, paged_attention) are
the genuinely structural ones: they implement single-token KV-cache
attention, the TPU analogue of the reference's decode kernels.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...nn import functional as F

# direct re-exports where the base framework already has the op
fused_rotary_position_embedding = F.fused_rotary_position_embedding
flash_attention = F.flash_attention
scaled_dot_product_attention = F.scaled_dot_product_attention


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, residual=None):
    """rms_norm(+optional residual add) — reference RmsNormKernel.
    ``begin_norm_axis``: normalize over axes [begin_norm_axis, ndim)."""
    if residual is not None:
        x = x + residual
    if begin_norm_axis in (-1, x.ndim - 1):
        out = F.rms_norm(x, norm_weight, epsilon)
    else:
        axes = tuple(range(begin_norm_axis % x.ndim, x.ndim))
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes,
                      keepdims=True)
        out = (x * jax.lax.rsqrt(ms + epsilon)).astype(x.dtype)
        if norm_weight is not None:
            out = out * norm_weight
    if norm_bias is not None:
        out = out + norm_bias
    return (out, x) if residual is not None else out


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     residual=None):
    if residual is not None:
        x = x + residual
    out = F.layer_norm(x, weight=norm_weight, bias=norm_bias,
                       epsilon=epsilon)
    return (out, x) if residual is not None else out


def fused_linear(x, weight, bias=None, transpose_weight=False):
    w = weight.T if transpose_weight else weight
    return F.linear(x, w, bias)


def fused_bias_act(x, bias=None, act_method="gelu"):
    if bias is not None:
        x = x + bias
    def _geglu(v):
        a, g = jnp.split(v, 2, axis=-1)
        return a * F.gelu(g)

    acts = {"gelu": F.gelu, "relu": F.relu, "silu": F.silu,
            "swiglu": F.swiglu, "geglu": _geglu}
    return acts[act_method](x)


def fused_dropout_add(x, y, p=0.0, training=True, mode="upscale_in_train"):
    return F.dropout(x, p, training=training, mode=mode) + y


def swiglu(x, y=None):
    return F.swiglu(x, y)


# ---------------------------------------------------------------------------
# fused-kernel library entry points (docs/KERNELS.md)
#
# Each op dispatches to its Pallas kernel on TPU (ops/pallas) and
# otherwise runs the XLA composition below — the composition IS the
# kernel's numerical contract (same op order, f32 accumulation), so the
# interpret-mode equivalence tests in tests/test_fused_kernels.py pin
# the two together.  Backward passes recompute through the composition
# (jax.vjp over the reference), the flash-attention remat recipe: the
# fused forward saves the HBM traffic, the backward pays one extra
# forward in exchange for standard XLA gradients.
# ---------------------------------------------------------------------------

def _prec(dtype):
    # HIGHEST only where it means something: the TPU MXU truncates f32
    # operands to bf16 by default (the int4_matmul note).  On CPU the
    # default f32 dot is already exact and HIGHEST picks a measurably
    # slower codegen path (autotune sweep, 2026-08-04: 57 → 37 ms on the
    # 350m MLP shape).
    return (jax.lax.Precision.HIGHEST
            if dtype == jnp.float32 and jax.default_backend() == "tpu"
            else None)


def _fused_swiglu_mlp_ref(x, w_gate, w_up, w_down):
    """XLA composition mirroring the fused_mlp kernel's numerics."""
    p = _prec(x.dtype)
    g = jax.lax.dot(x, w_gate.astype(x.dtype), precision=p,
                    preferred_element_type=jnp.float32)
    u = jax.lax.dot(x, w_up.astype(x.dtype), precision=p,
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jax.lax.dot(h, w_down.astype(x.dtype), precision=p,
                       preferred_element_type=jnp.float32).astype(x.dtype)


def _fused_swiglu_mlp_impl(x, w_gate, w_up, w_down, live=None):
    from ...ops import dispatch as _dispatch
    kernel = _dispatch.get("fused_swiglu_mlp")
    if kernel is not None:
        out = kernel(x, w_gate.astype(x.dtype), w_up.astype(x.dtype),
                     w_down.astype(x.dtype), live=live)
        if out is not None:
            return out
    return _fused_swiglu_mlp_ref(x, w_gate, w_up, w_down)


@jax.custom_vjp
def fused_swiglu_mlp(x, w_gate, w_up, w_down):
    """``silu(x @ Wg) · (x @ Wu) @ Wd`` in one pass — the (T, I) gate/up
    intermediate never round-trips HBM on TPU (ops/pallas/fused_mlp.py);
    XLA composition elsewhere.  x: (T, H); returns (T, H) in x.dtype."""
    return _fused_swiglu_mlp_impl(x, w_gate, w_up, w_down)


def _fused_swiglu_mlp_fwd(x, w_gate, w_up, w_down):
    return _fused_swiglu_mlp_impl(x, w_gate, w_up, w_down), \
        (x, w_gate, w_up, w_down)


def _fused_swiglu_mlp_bwd(res, ct):
    _, vjp = jax.vjp(_fused_swiglu_mlp_ref, *res)
    return vjp(ct)


fused_swiglu_mlp.defvjp(_fused_swiglu_mlp_fwd, _fused_swiglu_mlp_bwd)


def live_token_order(seq_lens, span: int):
    """Which lanes of the ragged serving step hold a token, once a step:
    ``seq_lens`` (B,) are the rows' span lengths, ``span`` the row width
    C.  Returns ``(order, inverse, n_live)`` over the ``B * C`` lanes:
    ``x[order]`` puts the live tokens first (a stable order),
    ``y[inverse]`` puts every lane back, ``n_live`` is their count.  By
    token, not by row: 32 decoding rows are 32 live tokens."""
    live = (jnp.arange(span)[None, :] < seq_lens[:, None]).reshape(-1)
    n_live = jnp.sum(live, dtype=jnp.int32)
    ahead = jnp.cumsum(live, dtype=jnp.int32)
    lane = jnp.arange(live.shape[0], dtype=jnp.int32)
    # a live lane goes after the live lanes before it, a dead one after
    # every live lane and the dead lanes before it
    inverse = jnp.where(live, ahead - 1, n_live + lane - ahead)
    order = jnp.zeros_like(lane).at[inverse].set(lane)
    return order, inverse, n_live


def fused_swiglu_mlp_live(x, w_gate, w_up, w_down, live):
    """``fused_swiglu_mlp`` for the ragged serving step, which knows its
    live lanes (``live`` = ``live_token_order(...)``): the kernel computes
    ``ceil(n_live / tile)`` token tiles under weights read once, and the
    dead lanes come back zero (``ops/pallas/fused_mlp.py``).  Where no
    kernel serves, the XLA composition computes every lane as
    ``fused_swiglu_mlp`` does: the live lanes' results are the same.
    Inference only (no vjp)."""
    return _fused_swiglu_mlp_impl(x, w_gate, w_up, w_down, live)


def _fused_rms_rope_qkv_ref(x, norm_weight, w_q, w_k, w_v, cos, sin,
                            head_dim, eps):
    """XLA composition mirroring the fused_norm_qkv kernel: rms-norm in
    f32, projections with f32 accumulation, rotate-half rope in f32.
    The kernel's selector-matmul rotation is exact (±1 entries), so the
    concat formulation here is the same arithmetic."""
    p = _prec(x.dtype)
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    nx = (xf * jax.lax.rsqrt(ms + eps)
          * norm_weight.astype(jnp.float32)).astype(x.dtype)

    def proj(w):
        return jax.lax.dot(nx, w.astype(x.dtype), precision=p,
                           preferred_element_type=jnp.float32)

    def rope(y):
        # rope runs on the x.dtype-ROUNDED projection (mirroring both
        # the kernel and the unfused path, where the projection layer's
        # output dtype is what the rotary pass sees), products in f32
        t, n = y.shape
        yh = y.astype(x.dtype).astype(jnp.float32) \
            .reshape(t, n // head_dim, head_dim)
        half = head_dim // 2
        rot = jnp.concatenate([-yh[..., half:], yh[..., :half]], axis=-1)
        c = cos.astype(jnp.float32)[:, None, :]
        s = sin.astype(jnp.float32)[:, None, :]
        return (yh * c + rot * s).reshape(t, n)

    q = proj(w_q)
    k = proj(w_k)
    return (rope(q).astype(x.dtype), rope(k).astype(x.dtype),
            proj(w_v).astype(x.dtype))


def _fused_rms_rope_qkv_impl(x, norm_weight, w_q, w_k, w_v, cos, sin,
                             head_dim, eps):
    from ...ops import dispatch as _dispatch
    kernel = _dispatch.get("fused_rms_rope_qkv")
    if kernel is not None:
        out = kernel(x, norm_weight, w_q.astype(x.dtype),
                     w_k.astype(x.dtype), w_v.astype(x.dtype), cos, sin,
                     head_dim, eps)
        if out is not None:
            return out
    return _fused_rms_rope_qkv_ref(x, norm_weight, w_q, w_k, w_v, cos,
                                   sin, head_dim, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def fused_rms_rope_qkv(x, norm_weight, w_q, w_k, w_v, cos, sin,
                       head_dim, eps=1e-5):
    """rms_norm → q/k/v projections → rotate-half rope on q/k in ONE
    pass over the hidden states (ops/pallas/fused_norm_qkv.py on TPU;
    XLA composition elsewhere).

    x: (T, H) flattened hidden states; norm_weight: (H,); w_q: (H, Nq);
    w_k/w_v: (H, Nk); cos/sin: (T, head_dim).  Returns ``(q, k, v)``
    with rope already applied to q and k, in ``x.dtype``.
    """
    return _fused_rms_rope_qkv_impl(x, norm_weight, w_q, w_k, w_v, cos,
                                    sin, head_dim, eps)


def _fused_rms_rope_qkv_fwd(x, norm_weight, w_q, w_k, w_v, cos, sin,
                            head_dim, eps):
    out = _fused_rms_rope_qkv_impl(x, norm_weight, w_q, w_k, w_v, cos,
                                   sin, head_dim, eps)
    return out, (x, norm_weight, w_q, w_k, w_v, cos, sin)


def _fused_rms_rope_qkv_bwd(head_dim, eps, res, ct):
    _, vjp = jax.vjp(
        lambda *a: _fused_rms_rope_qkv_ref(*a, head_dim, eps), *res)
    return vjp(ct)


fused_rms_rope_qkv.defvjp(_fused_rms_rope_qkv_fwd,
                          _fused_rms_rope_qkv_bwd)


def _lora_bgmv_ref(x, a, b, idx):
    """XLA composition mirroring the grouped-BGMV kernel's numerics
    (ops/pallas/lora_matmul.py): gather each slot's adapter blocks,
    shrink then expand with f32 accumulation, the rank-r intermediate
    rounded to ``x.dtype`` between the two dots.  Slot 0 rows multiply
    all-zero stacks, so their delta is EXACTLY 0.0 — adding it leaves
    base-only outputs bitwise unchanged."""
    p = _prec(x.dtype)
    ai = jnp.take(a, idx, axis=0).astype(x.dtype)      # (B, d_in, r)
    bi = jnp.take(b, idx, axis=0).astype(x.dtype)      # (B, r, d_out)
    h = jax.lax.dot_general(x, ai, (((2,), (1,)), ((0,), (0,))),
                            precision=p,
                            preferred_element_type=jnp.float32)
    h = h.astype(x.dtype)                              # (B, C, r)
    out = jax.lax.dot_general(h, bi, (((2,), (1,)), ((0,), (0,))),
                              precision=p,
                              preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def lora_bgmv(x, a, b, idx):
    """Grouped batched-gather matrix-vector product — the multi-LoRA
    serving delta ``x[s] @ A[idx[s]] @ B[idx[s]]`` per batch slot
    (docs/SERVING.md "Multi-LoRA").

    ``x`` is ``(B, C, d_in)`` (the projection's input span batch),
    ``a``/``b`` the stacked adapter pools ``(N, d_in, r)`` /
    ``(N, r, d_out)`` (``serving.LoRAPool.device_stacks``), ``idx``
    the per-slot adapter indices ``(B,)`` int32.  Mixed indices within
    one batch are the point; index 0 is the reserved exact no-op.
    Dispatches to the Pallas grouped-BGMV kernel on TPU (adapter blocks
    DMA'd by scalar-prefetched index, rank-r intermediate
    VMEM-resident); the gather+einsum composition above is the
    numerical contract and the fallback everywhere else.  Serving-only:
    no custom VJP (LoRA *training* is out of scope — deltas are jit
    inputs, not trained parameters here)."""
    from ...ops import dispatch as _dispatch
    kernel = _dispatch.get("lora_bgmv")
    if kernel is not None:
        out = kernel(x, a, b, idx)
        if out is not None:
            return out
    return _lora_bgmv_ref(x, a, b, idx)


def lora_delta(lora, inp, key):
    """The one adapter-delta call the model forwards share: resolve
    projection ``key`` in the threaded ``(layer pack, adapter ids)``
    pair and run :func:`lora_bgmv` on its stacks — ``None`` when no
    pack is threaded or the pool does not target this projection (the
    caller then skips the add outright)."""
    if lora is None:
        return None
    lpack, laids = lora
    e = lpack.get(key)
    if e is None:
        return None
    return lora_bgmv(inp, e["a"], e["b"], laids)


# ---------------------------------------------------------------------------
# decode attention (KV cache)
# ---------------------------------------------------------------------------

def quantize_kv(x):
    """THE int8 KV quantizer (symmetric, per-(…, head) over the last dim):
    returns (int8 values, f32 scales).  Shared by the decode write below,
    the model families' prefill writes, and the tests — one formula to
    change."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / 127.0 + 1e-12
    return jnp.round(xf / s[..., None]).astype(jnp.int8), s


def prefill_write_cache(cache, k, v, offset=0):
    """Write a prefill chunk at positions [offset, offset+s) into a dense
    cache tuple — 2-tuple fp or 4-tuple int8-quantized (see
    make_dense_caches)."""
    upd = jax.lax.dynamic_update_slice_in_dim
    if len(cache) == 4:
        kc, vc, ks, vs = cache
        k_q, ks_new = quantize_kv(k)
        v_q, vs_new = quantize_kv(v)
        return (upd(kc, k_q, offset, axis=1), upd(vc, v_q, offset, axis=1),
                upd(ks, ks_new, offset, axis=1),
                upd(vs, vs_new, offset, axis=1))
    kc, vc = cache
    return (upd(kc, k.astype(kc.dtype), offset, axis=1),
            upd(vc, v.astype(vc.dtype), offset, axis=1))


def read_cache_prefix(cache, length, dtype):
    """Read positions [0, length) from a dense cache tuple as ``dtype``
    K/V — dequantizing through the per-(position, head) scales for the
    int8 4-tuple layout.  Used by chunked prefill to attend over the
    already-cached prefix."""
    if len(cache) == 4:
        kc, vc, ks, vs = cache
        k = kc[:, :length].astype(dtype) * ks[:, :length, :, None].astype(dtype)
        v = vc[:, :length].astype(dtype) * vs[:, :length, :, None].astype(dtype)
        return k, v
    kc, vc = cache
    return kc[:, :length].astype(dtype), vc[:, :length].astype(dtype)


def decode_attend_cache(cache, q, new_k, new_v, seq_lens):
    """One decode step against a dense cache tuple — 2-tuple fp or
    4-tuple int8-quantized.  The single cache-arity dispatch shared by
    the model families.  Returns (out, new_cache)."""
    if len(cache) == 4:
        kc, vc, ks, vs = cache
        out, kc, vc, ks, vs = masked_multihead_attention(
            q, kc, vc, seq_lens, new_k, new_v, k_scale=ks, v_scale=vs)
        return out, (kc, vc, ks, vs)
    kc, vc = cache
    out, kc, vc = masked_multihead_attention(q, kc, vc, seq_lens,
                                             new_k, new_v)
    return out, (kc, vc)


def masked_multihead_attention(q, k_cache, v_cache, seq_lens,
                               new_k=None, new_v=None, scale=None,
                               k_scale=None, v_scale=None,
                               uniform_lens=False):
    """Single-step decode attention against a dense KV cache.

    Reference: MaskedMultiheadAttentionKernel
    (paddle/phi/kernels/fusion/gpu/, SURVEY §2.1 fused kernels row; the
    reference kernel also carries the int8 cache_kv_quant path).

    q:        (B, H, D)        — the new token's query
    k_cache:  (B, S_max, H_kv, D) — updated IN-PLACE-style: returns new cache
    seq_lens: (B,)             — current lengths (position of the new token)
    new_k/new_v: (B, H_kv, D)  — this step's k/v, written at seq_lens
    k_scale/v_scale: (B, S_max, H_kv) f32 — present iff the caches are
    int8-quantized (per-position, per-head symmetric scales).  Decode is
    HBM-bandwidth-bound, so int8 caches halve the dominant traffic; the
    dequant multiply fuses into the einsum operand load.

    Returns (out, k_cache, v_cache) — plus the updated scales when
    quantized: (out, k_cache, v_cache, k_scale, v_scale).
    """
    b, h, d = q.shape
    s_max = k_cache.shape[1]
    h_kv = k_cache.shape[2]
    quantized = k_scale is not None
    if new_k is not None:
        # One-token cache write.  Measured on-chip (v5e, bs8 decode,
        # docs/BENCH.md): the "where" full-cache rewrite STREAMS at HBM
        # bandwidth and beats both indexed alternatives —
        # dynamic_update_slice at a traced start (4.0/7.6 ms bf16/int8 per
        # step: the traced index defeats in-place aliasing inside the scan,
        # so XLA copies the cache) and per-row scatter (3.5/5.7 ms) vs
        # where at 3.0/1.4-2.7 ms.  PDTPU_MMA_WRITE=where|slice|scatter
        # keeps the experiment reproducible.
        if quantized:
            k_q, ks_new = quantize_kv(new_k)
            v_q, vs_new = quantize_kv(new_v)
            writes = [("k", k_q), ("v", v_q),
                      ("ks", ks_new), ("vs", vs_new)]
        else:
            # cast to the cache dtype: mixing dtypes here would silently
            # promote the whole cache (and break scan carries holding it)
            writes = [("k", new_k.astype(k_cache.dtype)),
                      ("v", new_v.astype(v_cache.dtype))]
        import os as _os
        strategy = _os.environ.get("PDTPU_MMA_WRITE", "where")
        if strategy not in ("where", "slice", "scatter"):
            raise ValueError(
                f"PDTPU_MMA_WRITE={strategy!r}: expected "
                "where|slice|scatter")
        # slice writes ONE slab at seq_lens[0]: only valid when every
        # row's length advances in lockstep.  Callers that KNOW this pass
        # uniform_lens=True; PDTPU_MMA_UNIFORM=1 is the operator's
        # equivalent assertion for the generate() A/B (the model families
        # cannot see whether their caller is the lockstep decode loop).
        if strategy == "slice":
            uniform_lens = (uniform_lens or
                            _os.environ.get("PDTPU_MMA_UNIFORM") == "1")
            if not uniform_lens:
                raise ValueError(
                    "PDTPU_MMA_WRITE=slice requires lockstep lens: pass "
                    "uniform_lens=True (op callers) or set "
                    "PDTPU_MMA_UNIFORM=1 (generate() benchmarking) — "
                    "ragged lens would be silently corrupted")
        caches = {"k": k_cache, "v": v_cache, "ks": k_scale, "vs": v_scale}
        for name, val in writes:
            if strategy == "slice":
                caches[name] = jax.lax.dynamic_update_slice_in_dim(
                    caches[name], val[:, None], seq_lens[0], axis=1)
            elif strategy == "where":
                onemask = (jnp.arange(s_max)[None, :] ==
                           seq_lens[:, None])
                shaped = onemask[(...,) + (None,) * (val.ndim - 1)]
                caches[name] = jnp.where(shaped, val[:, None], caches[name])
            else:
                caches[name] = caches[name].at[
                    jnp.arange(q.shape[0]), seq_lens].set(val, mode="drop")
        k_cache, v_cache = caches["k"], caches["v"]
        k_scale, v_scale = caches["ks"], caches["vs"]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    g = h // h_kv
    if quantized:
        k_read = k_cache.astype(jnp.bfloat16) * \
            k_scale.astype(jnp.bfloat16)[..., None]
        v_read = v_cache.astype(jnp.float32) * v_scale[..., None]
    else:
        k_read, v_read = k_cache, v_cache
    # GQA without materializing repeated KV: group the q heads per kv head
    # and contract against the kv head axis directly (4x less HBM traffic
    # at 4-way GQA); accumulate in fp32 on the MXU
    qg = q.reshape(b, h_kv, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_read,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s_max)[None, None, None, :] <= \
        seq_lens[:, None, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # probs stay fp32 through the PV contraction (decode is bandwidth-bound;
    # bf16-rounding the probabilities would cost accuracy for nothing)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_read,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, h, d).astype(q.dtype)
    if quantized:
        return out, k_cache, v_cache, k_scale, v_scale
    return out, k_cache, v_cache


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    scale: Optional[float] = None):
    """Decode attention over a PAGED (block) KV cache — vLLM-style serving.

    Reference capability: paged/block attention in the reference serving
    stack (PaddleNLP inference; core provides the fused decode kernels).

    q:            (B, H, D)
    k_cache/v_cache: (num_blocks, block_size, H_kv, D) — global block pool
    block_tables: (B, max_blocks_per_seq) int32 — per-seq block ids
    context_lens: (B,) — tokens so far (incl. current)

    On TPU this dispatches to the Pallas kernel
    (ops/pallas/decode_attention.py) whose scalar-prefetched block table
    DMAs each page straight from the pool — the XLA gather below
    materializes the gathered cache and is orders of magnitude slower
    on TPU; it remains the CPU/fallback reference implementation.
    """
    from ...ops import dispatch as _dispatch
    kernel = _dispatch.get("paged_attention")
    if kernel is not None:
        out = kernel(q, k_cache, v_cache, block_tables, context_lens,
                     scale=scale)
        if out is not None:
            return out
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    k, v = _paged_gather_dense(k_cache, v_cache, block_tables)
    return _attend_dense_gqa(q, k, v, context_lens, scale)


def write_paged_kv(k_cache, v_cache, new_k, new_v, block_tables,
                   context_lens) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter this step's (B, H_kv, D) k/v into the paged pool at position
    context_lens-1 of each sequence."""
    b = new_k.shape[0]
    bs = k_cache.shape[1]
    pos = context_lens - 1
    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None],
                              axis=1)[:, 0]
    off = pos % bs
    k_cache = k_cache.at[blk, off].set(new_k)
    v_cache = v_cache.at[blk, off].set(new_v)
    return k_cache, v_cache


def _paged_gather_dense(k_cache, v_cache, block_tables, k_scale=None,
                        v_scale=None):
    """Gather a batch's pages from the pool into dense (B, S, H_kv, D)
    fp32 K/V — dequantizing through the per-(position, head) scales for
    int8 pools.  Only the gathered blocks materialize, never the pool."""
    nb, bs, h_kv, d = k_cache.shape
    b, mb = block_tables.shape
    k = k_cache[block_tables].reshape(b, mb * bs, h_kv, d)
    v = v_cache[block_tables].reshape(b, mb * bs, h_kv, d)
    if k_scale is not None:
        k = k.astype(jnp.float32) * \
            k_scale[block_tables].reshape(b, mb * bs, h_kv)[..., None]
        v = v.astype(jnp.float32) * \
            v_scale[block_tables].reshape(b, mb * bs, h_kv)[..., None]
    return k, v


def _attend_dense_gqa(q, k, v, context_lens, scale):
    """Masked decode attention over dense (B, S, H_kv, D) K/V without
    repeating KV across the GQA groups (shared by the paged fallbacks)."""
    b, h, d = q.shape
    s = k.shape[1]
    h_kv = k.shape[2]
    g = h // h_kv
    qg = q.reshape(b, h_kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s)[None, None, None, :] < \
        context_lens[:, None, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, d).astype(q.dtype)


def _paged_span_write(cache, k, v, block_tables, span_starts, span_lens):
    """Scatter a token span ``k``/``v`` (B, C, H_kv, D) into the paged
    pools at positions ``[span_starts, span_starts + span_lens)`` of each
    sequence.  Rows ``>= span_lens`` (chunk padding, idle slots) get an
    out-of-range block id and are DROPPED by the scatter, so padding
    never lands in the pool.  Shared cache-arity dispatch (fp 2-tuple or
    int8 4-tuple)."""
    b, s = k.shape[:2]
    nb, bs = cache[0].shape[:2]
    mb = block_tables.shape[1]
    pos = span_starts[:, None] + jnp.arange(s)[None, :]       # (B, C)
    blk = jnp.take_along_axis(block_tables, jnp.minimum(pos // bs, mb - 1),
                              axis=1)
    live = jnp.arange(s)[None, :] < span_lens[:, None]
    blk = jnp.where(live, blk, nb)                            # OOB → dropped
    off = pos % bs
    if len(cache) == 4:
        kc, vc, ks, vs = cache
        k_q, ks_new = quantize_kv(k)
        v_q, vs_new = quantize_kv(v)
        return (kc.at[blk, off].set(k_q), vc.at[blk, off].set(v_q),
                ks.at[blk, off].set(ks_new), vs.at[blk, off].set(vs_new))
    kc, vc = cache
    return (kc.at[blk, off].set(k.astype(kc.dtype)),
            vc.at[blk, off].set(v.astype(vc.dtype)))


def _ragged_attend_dense(q, k, v, span_starts, scale, skips=None, page=None):
    """Span attention over dense gathered (B, S, H_kv, D) K/V: query row
    ``j`` of slot ``b`` (position ``span_starts[b] + j``) attends over
    positions ``[0, span_starts[b] + j]``.  GQA without repeating KV,
    fp32 accumulation — the (B, C)-shaped analogue of
    :func:`_attend_dense_gqa` (shared by the ragged fallbacks).  With
    ``skips`` ``(B,)`` slot ``b`` sees nothing from position
    ``skips[b]`` to the end of the ``page`` it lies in (the XLA
    composition of the ragged kernel's operand of that name:
    :func:`eva_paged_attend`)."""
    b, c, h, d = q.shape
    s = k.shape[1]
    h_kv = k.shape[2]
    g = h // h_kv
    qg = q.reshape(b, c, h_kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bckgd,bskd->bckgs", qg, k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    pos = span_starts[:, None] + jnp.arange(c)[None, :]       # (B, C)
    # position 0 is always visible (pos >= 0), so no row softmaxes over
    # an empty set — dead rows produce finite garbage the caller discards
    mask = jnp.arange(s)[None, None, :] <= pos[:, :, None]    # (B, C, S)
    if skips is not None:
        key, lo = jnp.arange(s)[None, :], skips[:, None]
        seen = (key < lo) | (key >= -(-lo // page) * page)    # (B, S)
        mask = mask & seen[:, None, :]
    scores = jnp.where(mask[:, :, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bckgs,bskd->bckgd", probs, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, h, d).astype(q.dtype)


def ragged_paged_attend(cache, q, new_k, new_v, block_tables, span_starts,
                        span_lens, scale: Optional[float] = None):
    """ONE serving step for a ragged batch of token spans: prefill
    chunks and decode tokens in one dispatch (PAPERS.md "Ragged Paged
    Attention").

    Each slot ``b`` carries a span of ``span_lens[b]`` tokens starting at
    pool position ``span_starts[b]``: a chunked-prefill segment
    (``len > 1``), a single decode token (``len == 1``), or nothing
    (``len == 0`` — idle or dead slot; with an out-of-range block table
    its writes drop and its garbage output is discarded, so nothing a
    dead slot does can corrupt live blocks).

    ``q``/``new_k``/``new_v`` are ``(B, C, H|H_kv, D)``; the span's k/v
    is written at ``[start, start + len)`` and query row ``j`` attends
    over pool positions ``[0, start + j]`` — the cached prefix plus the
    causal part of its own span.  ``cache`` is the per-layer pool tuple
    (fp 2-tuple or int8 4-tuple with :func:`quantize_kv` scales); int8
    pools attend through the XLA gather+dequant formulation on every
    backend (the Pallas kernel is fp-only), fp pools dispatch to the
    ragged Pallas kernel on TPU.

    Returns ``(out (B, C, H, D), new_cache)``.
    """
    if span_starts is None:
        raise ValueError(
            "paged KV pools (block_tables) are served by the ragged step "
            "only: pass span_starts with them")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    new_cache = _paged_span_write(cache, new_k, new_v, block_tables,
                                  span_starts, span_lens)
    if len(new_cache) == 4:
        kc, vc, ks, vs = new_cache
        kd, vd = _paged_gather_dense(kc, vc, block_tables, ks, vs)
        return (_ragged_attend_dense(q, kd, vd, span_starts, scale),
                new_cache)
    kc, vc = new_cache
    from ...ops import dispatch as _dispatch
    kernel = _dispatch.get("ragged_paged_attention")
    if kernel is not None:
        out = kernel(q, kc, vc, block_tables, span_starts, span_lens,
                     scale=scale)
        if out is not None:
            return out, new_cache
    kd, vd = _paged_gather_dense(kc, vc, block_tables)
    return _ragged_attend_dense(q, kd, vd, span_starts, scale), new_cache


def eva_chunk_summaries(k, v, phi, mu, scale):
    """EVA's chunk summariser (``models/evabyte.py``): ``k``, ``v``
    ``(..., c, H, D)``, the ``c`` positions of one chunk a leading index;
    ``phi``, ``mu`` ``(H, D)``.  Weights ``softmax over the chunk of
    (scale * phi_h . k_m)``; returns ``(ktilde = weighted keys + mu,
    vtilde = weighted values)``, each ``(..., H, D)`` in float32."""
    p = _prec(k.dtype)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    sc = jnp.einsum("hd,...mhd->...mh", phi.astype(jnp.float32), kf,
                    precision=p) * scale
    w = jax.nn.softmax(sc, axis=-2)
    kt = jnp.einsum("...mh,...mhd->...hd", w, kf, precision=p) \
        + mu.astype(jnp.float32)
    vt = jnp.einsum("...mh,...mhd->...hd", w, vf, precision=p)
    return kt, vt


def _eva_write_summaries(kc, vc, block_tables, cache_starts, summary_dst,
                         phi, mu, scale):
    """Summarise every chunk that a row's span completes, from the page as
    the pool holds it, and write ``(ktilde, vtilde)`` at its summary row.

    ``summary_dst`` ``(B, M)``: slot ``i`` of row ``b`` is the chunk whose
    window page is entry ``cache_starts[b] // page + i`` of the row's
    table; its value is ``block * page + row`` of the summary's place in
    the pool, or ``num_blocks * page`` where the span does not end that
    chunk (the write drops to the out-of-range block, as a dead slot's
    do)."""
    nb, page = kc.shape[:2]
    mb = block_tables.shape[1]
    m = summary_dst.shape[1]
    idx = cache_starts[:, None] // page + jnp.arange(m)[None, :]
    src = jnp.take_along_axis(block_tables, jnp.minimum(idx, mb - 1), axis=1)
    # an out-of-range table entry clamps to a real page under the gather;
    # what is computed from it goes nowhere
    kt, vt = eva_chunk_summaries(kc[src], vc[src], phi, mu, scale)
    blk, row = summary_dst // page, summary_dst % page
    return (kc.at[blk, row].set(kt.astype(kc.dtype)),
            vc.at[blk, row].set(vt.astype(vc.dtype)))


def eva_paged_attend(cache, q, new_k, new_v, block_tables, span_lens, aux,
                     phi, mu, scale: Optional[float] = None):
    """ONE serving step of EVA, chunked linearized attention
    (``models/evabyte.py``), for a ragged batch of token spans, beside
    :func:`ragged_paged_attend`.

    A request's cache is two kinds of page of ONE geometry in one pool:
    exact k/v pages of its open window, and summary pages holding one
    ``(ktilde, vtilde)`` row per completed chunk.  A row's table lists
    the summary pages that hold a row of a window before the span's,
    then the pages of the span's own window.  ``aux`` is what
    ``serving.block_allocator.WindowSummarySpec.step_aux`` made of the
    step's plan: ``summary_rows`` ``(B,)``, the summaries the row's
    queries see, ``(W / c) * w`` of them; ``cache_starts`` ``(B,)``, the
    span's start counted along the table (the summary pages, then the
    span's offset in its window); ``summary_dst`` ``(B, C / c)``, where
    the summaries of the chunks that the span completes go.  Causal
    attention along the table, but for the rows of the last summary page
    from ``summary_rows`` on, is then EVA's: every summary of an earlier
    window, the own window's keys up to the query, one softmax.  The
    step, in order:

    1. write the spans' k/v at ``[cache_starts, cache_starts + lens)``;
    2. for every chunk a span completes, summarise it from the page as
       the pool now holds it and write the summary's row: a later row of
       the same step, past a window boundary, reads it in step 3;
    3. attend: the ragged kernel over the row's table on TPU (its events
       are named ``eva_ragged_paged_attention``), the XLA gather
       composition elsewhere.

    ``q``/``new_k``/``new_v`` are ``(B, C, H, D)``; ``phi``/``mu``
    ``(H, D)``.  Returns ``(out (B, C, H, D), new_cache)``.
    """
    if len(cache) != 2:
        raise NotImplementedError(
            "EVA's window+summary cache has float pools only: a summary "
            "row has no per-position scale to be quantized with")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    starts, rows = aux["cache_starts"], aux["summary_rows"]
    kc, vc = _paged_span_write(cache, new_k, new_v, block_tables, starts,
                               span_lens)
    with jax.named_scope("eva_summarise"):
        kc, vc = _eva_write_summaries(kc, vc, block_tables, starts,
                                      aux["summary_dst"], phi, mu, scale)
    with jax.named_scope("eva_attend"):
        from ...ops import dispatch as _dispatch
        kernel = _dispatch.get("eva_ragged_paged_attention")
        if kernel is not None:
            out = kernel(q.astype(kc.dtype), kc, vc, block_tables, starts,
                         span_lens, rows, scale=scale)
            if out is not None:
                return out.astype(q.dtype), (kc, vc)
        kd, vd = _paged_gather_dense(kc, vc, block_tables)
        return (_ragged_attend_dense(q, kd, vd, starts, scale, skips=rows,
                                     page=kc.shape[1]), (kc, vc))


def _gated_delta_rule_ref(q, k, v, g, beta, chunk: int = 64):
    """The chunked gated delta rule as an XLA composition: the path of
    :func:`gated_delta_rule` where no kernel serves, and the yardstick the
    kernel is held to.

    Nothing runs position by position.  Within a chunk of ``chunk``
    positions, with ``G`` the running sum of ``g`` and ``M[i, j] =
    exp(G_i - G_j)`` for ``i >= j``, the chunk's own updates solve one
    unit lower-triangular system ``(I + strict_lower((k beta) k^T * M))
    [V' | K'] = [v beta | k beta exp(G)]``; everything that does not read
    ``S`` is computed for all chunks at once, and a ``lax.scan`` over the
    chunks carries ``S`` through four products a chunk::

        v_new = V' - K' S;  o = (q exp(G)) S + lower(q k^T * M) v_new
        S <- exp(G_C) S + (k exp(G_C - G))^T v_new

    Every exponent is <= 0, so a strongly negative ``g`` underflows to an
    exact forgetting and nothing overflows.  Float32 throughout, ``o``
    included (whatever the operands' dtypes: they are cast on entry); the
    triangular system at full precision, the products that read ``S`` at
    the precision of :func:`_prec` of ``q``'s dtype.  The backward pass is
    autodiff through the scan: one ``S`` and one ``v_new`` a chunk are
    kept (``S / chunk`` x ``H (dk + chunk) dv`` float32 numbers)."""
    from jax.scipy.linalg import solve_triangular

    b, s, hk, dk = q.shape
    h, dv = v.shape[2:]
    if h != hk:
        q, k = (jnp.repeat(x, h // hk, axis=2) for x in (q, k))
    p = _prec(q.dtype)
    hi = jax.lax.Precision.HIGHEST
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(x):
        """(B, S, H, ...) -> float32 (B, H, n, chunk, ...)"""
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    with jax.named_scope("gated_delta_rule"):
        q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
        big = jnp.cumsum(g, axis=-1)                          # G
        i = jnp.arange(chunk)
        lower = i[:, None] >= i[None, :]
        # the mask goes on the exponent: exp of a masked-out positive
        # difference would overflow, and its zero cotangent would be NaN
        m = jnp.exp(jnp.where(lower, big[..., :, None] - big[..., None, :],
                              -jnp.inf))
        kb = k * beta[..., None]
        system = jnp.where(i[:, None] > i[None, :],
                           jnp.einsum("...id,...jd->...ij", kb, k,
                                      precision=hi) * m, 0.0) \
            + jnp.eye(chunk, dtype=jnp.float32)
        rhs = jnp.concatenate(
            [v * beta[..., None], kb * jnp.exp(big)[..., None]], axis=-1)
        solved = solve_triangular(system, rhs, lower=True,
                                  unit_diagonal=True)
        v_own, k_own = solved[..., :dv], solved[..., dv:]
        qk = jnp.einsum("...id,...jd->...ij", q, k, precision=p) * m
        q_in = q * jnp.exp(big)[..., None]
        last = big[..., -1]                                   # G_C
        k_out = k * jnp.exp(last[..., None] - big)[..., None]
        decay = jnp.exp(last)

        def step(state, xs):
            v_own, k_own, qk, q_in, k_out, decay = xs
            v_new = v_own - jnp.einsum("bhik,bhkv->bhiv", k_own, state,
                                       precision=p)
            o = jnp.einsum("bhik,bhkv->bhiv", q_in, state, precision=p) \
                + jnp.einsum("bhij,bhjv->bhiv", qk, v_new, precision=p)
            state = decay[..., None, None] * state \
                + jnp.einsum("bhik,bhiv->bhkv", k_out, v_new, precision=p)
            return state, o

        xs = tuple(jnp.moveaxis(x, 2, 0)
                   for x in (v_own, k_own, qk, q_in, k_out, decay))
        _, o = jax.lax.scan(
            step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
        o = jnp.moveaxis(o, 0, 2)                             # (B,H,n,C,dv)
        o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)
    return o[:, :s]


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """The gated delta rule (Gated DeltaNet; ``models/qwen3_next.py``) in
    its chunked form.  Per head, with a state ``S`` of ``(dk, dv)`` from
    zero, position ``t`` does::

        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);
        S <- S + k_t d^T;  o_t = S^T q_t

    ``q``, ``k`` ``(B, S, Hk, dk)`` as the rule reads them (normalised and
    scaled by the caller), ``v`` ``(B, S, Hv, dv)`` with ``Hv`` a multiple
    of ``Hk`` (key head ``h // (Hv / Hk)`` serves value head ``h``), ``g``
    (the log decay, <= 0) and ``beta`` ``(B, S, Hv)``.  Returns ``o``
    ``(B, S, Hv, dv)`` in float32 on either path (the state is float32
    and the caller's gated norm reads it so).  A sequence that is no multiple
    of ``chunk`` is padded with positions that neither write the state nor
    are read back (``k = beta = g = 0``).

    Which path runs is read from the operands.  On TPU, with head sizes
    that are multiples of 128, ``chunk`` 64, an even number of value heads
    to a key head and no active mesh: the Pallas kernel pair of
    ``ops/pallas/gated_delta.py`` under a ``jax.custom_vjp``: a chunk's
    triangular system and ``S`` stay in VMEM; the triangular system in
    float32 at full precision, the products that read or write ``S`` at
    the precision the composition gives them (by ``q``'s dtype: float32 q
    and k at ``HIGHEST``); the backward pass is a kernel too and the
    forward keeps for it, a chunk, the state it started from and the
    system's inverse (``S / chunk`` x ``Hv (dk dv + 2 chunk^2)`` float32
    numbers).  Elsewhere (the CPU, a mesh, other shapes):
    :func:`_gated_delta_rule_ref`, the same chunked algorithm as an XLA
    composition under ``jax.checkpoint``, so that only its operands are
    kept for autodiff's backward and its batched intermediates (2.5 GB a
    layer at 8,192 positions of 32 heads) are recomputed there."""
    from ...ops import dispatch as _dispatch
    kernel = _dispatch.get("gated_delta_rule")
    if kernel is not None:
        out = kernel(q, k, v, g, beta, chunk)
        if out is not None:
            return out
    return jax.checkpoint(
        functools.partial(_gated_delta_rule_ref, chunk=chunk))(
            q, k, v, g, beta)


def grouped_matmul(x, w, group_sizes):
    """One product over a stack of matrices, the rows grouped as data:
    ``x`` ``(M, K)`` sorted by group, ``w`` ``(G, K, N)``,
    ``group_sizes`` ``(G,)`` int32; rows ``[sum(sizes[:g]),
    sum(sizes[:g + 1]))`` are multiplied with ``w[g]``, rows past
    ``sum(sizes)`` come back zero.  ``jax.lax.ragged_dot``: XLA's TPU
    compiler lowers it to its own grouped-matmul kernel, which walks the
    row tiles that hold a group's rows (their number is data), forward
    and in both gradients; elsewhere it is a masked composition.  That
    kernel leaves the rows past the groups as it finds them (whatever the
    buffer held, NaN included), forward and in the gradient with respect
    to ``x``; the select here, and its transpose on the way back, keep
    such rows out of every operand of every product."""
    sizes = group_sizes.astype(jnp.int32)
    y = jax.lax.ragged_dot(x, w.astype(x.dtype), sizes)
    return jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None], y,
                     jnp.zeros((), y.dtype))


def paged_copy_blocks(cache, src_blocks, dst_blocks):
    """Copy whole pages ``src_blocks[i] → dst_blocks[i]`` inside the
    paged pools — the device half of copy-on-write block sharing
    (serving/block_allocator.py).  Fixed-shape: pad unused entries with
    the out-of-range sentinel (``num_blocks``) — OOB destinations DROP
    and OOB sources clamp to a real page that is then never written.
    Shared cache-arity dispatch; returns the new cache tuple."""
    return tuple(a.at[dst_blocks].set(a[src_blocks]) for a in cache)


def variable_length_memory_efficient_attention(q, k, v, seq_lens=None,
                                               kv_seq_lens=None, mask=None,
                                               scale=None, causal=False):
    """Varlen attention (reference cutlass memory_efficient_attention):
    here, flash/XLA attention with a length mask."""
    if mask is None and (seq_lens is not None or kv_seq_lens is not None):
        sk = k.shape[1]
        # mask only the KEY axis: fully-masked query rows would softmax over
        # all -inf and emit NaN; padded query outputs are instead left as
        # attention over the valid keys and callers drop them
        klens = kv_seq_lens if kv_seq_lens is not None else seq_lens
        km = jnp.arange(sk)[None] < klens[:, None]
        mask = jnp.where(km[:, None, None, :], 0.0, -jnp.inf)
    return F.scaled_dot_product_attention(q, k, v, attn_mask=mask,
                                          is_causal=causal)


def fused_moe(x, gate_weight, ffn1_weights, ffn2_weights, ffn1_biases=None,
              ffn2_biases=None, moe_topk=2, norm_topk_prob=True,
              act="silu_glu"):
    """Reference: paddle.incubate.nn.functional.fused_moe — one fused op
    for topk gating + per-expert FFN + weighted combine.

    TPU formulation: every token runs EVERY expert densely
    (``einsum('nh,ehi->nei')`` — weights stay (E, H, *), activations are
    the N×E×I transient) and the top-k probabilities zero out the
    non-selected experts in the combine.  Gathering per-token weight
    copies (``w[topi]``) would materialize N×K full weight matrices —
    terabytes at Mixtral scale.  The dense form trades E/K× extra FLOPs
    for static shapes and no routing; for large-scale training use
    MoELayer's capacity-based dispatch (distributed/moe.py), which is
    the ep-sharded production path.

    Shapes: x (..., H); gate_weight (H, E); ffn1_weights (E, H, 2I) for
    the silu-glu act (gate|up packed) or (E, H, I); ffn2_weights
    (E, I, H).  Returns (..., H).
    """
    import jax

    orig = x.shape
    H = orig[-1]
    t = x.reshape(-1, H)                                    # (N, H)
    logits = t.astype(jnp.float32) @ jnp.asarray(gate_weight,
                                                 jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (N, E)
    E = probs.shape[-1]
    topv, topi = jax.lax.top_k(probs, moe_topk)             # (N, K)
    if norm_topk_prob:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    # (N, E) combine weights: top-k probs scattered back, zeros elsewhere
    combine = jnp.sum(jax.nn.one_hot(topi, E, dtype=topv.dtype)
                      * topv[..., None], axis=1)            # (N, E)

    w1 = jnp.asarray(ffn1_weights)
    w2 = jnp.asarray(ffn2_weights)
    h1 = jnp.einsum("nh,ehi->nei", t, w1.astype(t.dtype))
    if ffn1_biases is not None:
        h1 = h1 + jnp.asarray(ffn1_biases)[None].astype(h1.dtype)
    if act == "silu_glu":
        gate_part, up = jnp.split(h1, 2, axis=-1)
        h1 = jax.nn.silu(gate_part) * up
    elif act == "gelu":
        h1 = jax.nn.gelu(h1)
    else:
        h1 = jax.nn.silu(h1)
    h2 = jnp.einsum("nei,eih->neh", h1, w2.astype(h1.dtype))
    if ffn2_biases is not None:
        h2 = h2 + jnp.asarray(ffn2_biases)[None].astype(h2.dtype)
    out = jnp.einsum("neh,ne->nh", h2, combine.astype(h2.dtype))
    return out.reshape(orig).astype(x.dtype)
