"""Llama model family (flagship; BASELINE.json configs[0]/[4]).

Reference capability: PaddleNLP's LlamaForCausalLM expressed with the core
framework's fleet layers (the reference core provides the layers; the model
zoo lives in PaddleNLP — SURVEY.md §0 scope note).  Built here TPU-first:

- tensor parallel via ColumnParallel/RowParallel/VocabParallelEmbedding
  partition specs ("mp" axis), degrading to serial when mp=1;
- Megatron-SP sequence sharding of norm/residual activations (sep §5.7-2);
- GQA + RoPE + flash-attention dispatch (Pallas kernel on TPU);
- optional per-layer rematerialisation;
- everything jit-compiles into one XLA program via TrainStep.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..observability.regions import region
from ..distributed.mp_layers import (ColumnParallelLinear, ParallelCrossEntropy,
                                     RowParallelLinear, VocabParallelEmbedding,
                                     constrain)
from ..distributed.recompute import RecomputeWrapper
from .generation import CachedGenerationMixin


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    use_recompute: bool = False
    recompute_policy: Optional[str] = None  # full recompute; "dots" saves s×s attn probs = OOM at long seq
    recompute_num_layers: Optional[int] = None  # Megatron-style partial remat: only the first N layers
    sequence_parallel: bool = False
    context_parallel: Optional[str] = None  # None | "ring" | "ulysses" (sep axis)
    pipeline_stages: int = 1        # >1: stacked pp-sharded decoder body
    num_microbatches: Optional[int] = None  # default: pipeline_stages
    virtual_pp_degree: int = 1      # interleaved-schedule chunks per stage
    loss_seq_chunks: int = 1        # >1: rematerialized seq-chunked vocab CE
    # fused-kernel library (docs/KERNELS.md): "on" takes incubate's fused
    # norm+rope+qkv and swiglu-MLP entries everywhere (Pallas kernel on TPU,
    # same-numerics XLA composition elsewhere); "auto" only where the kernel
    # will serve (TPU, no mesh, supported(), no tuned veto); "off" never
    fused_ops: str = "auto"
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def num_params(self) -> int:
        h, i, v, l = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_hidden_layers)
        kvh = self.num_key_value_heads * self.head_dim
        per_layer = h * h + 2 * h * kvh + h * h + 3 * h * i + 2 * h
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        return l * per_layer + embed + h


PRESETS = {
    "llama2-7b": LlamaConfig(),
    "llama2-13b": LlamaConfig(hidden_size=5120, intermediate_size=13824,
                              num_hidden_layers=40, num_attention_heads=40,
                              num_key_value_heads=40),
    "llama2-70b": LlamaConfig(hidden_size=8192, intermediate_size=28672,
                              num_hidden_layers=80, num_attention_heads=64,
                              num_key_value_heads=8),
    "llama-1b": LlamaConfig(hidden_size=2048, intermediate_size=5504,
                            num_hidden_layers=16, num_attention_heads=16,
                            num_key_value_heads=16, vocab_size=32000),
    "llama-350m": LlamaConfig(hidden_size=1024, intermediate_size=2816,
                              num_hidden_layers=24, num_attention_heads=16,
                              num_key_value_heads=16),
    # same parameter count as llama-350m but 8 heads of head_dim 128 — the
    # north-star's (Llama-2-7B) attention geometry, where qk/sv matmuls
    # fill the 128-wide MXU instead of running K/N=64 at half occupancy
    "llama-350m-hd128": LlamaConfig(hidden_size=1024, intermediate_size=2816,
                                    num_hidden_layers=24,
                                    num_attention_heads=8,
                                    num_key_value_heads=8),
    "tiny": LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, max_position_embeddings=128),
}


def _weight_attr(cfg: LlamaConfig):
    # reference Llama init: Normal(0, initializer_range) on every projection
    from ..nn.layer import ParamAttr
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


def _use_fused(cfg, op: str, key=None, probe=None, layers=()) -> bool:
    """Trace-time fused-op resolution (ops.tuning owns the policy).

    Sequence-parallel keeps the unfused path (the fused entry points
    bypass the Column/RowParallel scatter-gather the sp layout needs),
    and so does ANY quantized projection in ``layers`` — weight-only
    quantized layers keep raw int8/int4 codes in ``.weight`` with the
    scale in a separate buffer, so the fused entries (which read
    ``.weight`` directly) would silently drop the scales; their decode
    fusion is the int8/int4 matmul kernel inside the layer's own
    forward instead.  ``probe`` (called only under ``"auto"``) is the
    kernel's ``supported()`` shape gate: auto means "only where a
    kernel will actually serve", so a geometry the kernel declines
    (e.g. llama-1b's VMEM overflow) keeps the cheaper unfused path
    rather than paying the fused entry's recompute backward for an XLA
    composition forward."""
    if getattr(cfg, "sequence_parallel", False):
        return False
    if any(hasattr(l, "weight_scale") for l in layers):
        return False
    from ..ops import tuning
    mode = getattr(cfg, "fused_ops", "off")
    if not tuning.fusion_enabled(mode, op, key):
        return False
    if mode == "auto" and probe is not None and not probe():
        return False
    return True


class LlamaRMSNorm(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.eps = cfg.rms_norm_eps
        self.weight = self.create_parameter(
            (cfg.hidden_size,), default_initializer=I.Constant(1.0))

    def forward(self, x):
        with region("norm"):
            return F.rms_norm(x, self.weight, self.eps)


class LlamaAttention(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h, hd = cfg.hidden_size, cfg.head_dim
        kv = cfg.num_key_value_heads * hd
        attr = _weight_attr(cfg)
        sp = cfg.sequence_parallel
        self.q_proj = ColumnParallelLinear(h, h, has_bias=False,
                                           weight_attr=attr, sequence_parallel=sp)
        self.k_proj = ColumnParallelLinear(h, kv, has_bias=False,
                                           weight_attr=attr, sequence_parallel=sp)
        self.v_proj = ColumnParallelLinear(h, kv, has_bias=False,
                                           weight_attr=attr, sequence_parallel=sp)
        self.o_proj = RowParallelLinear(h, h, has_bias=False,
                                        weight_attr=attr, sequence_parallel=sp)

    def forward(self, x, cos, sin, attn_mask=None, cache=None,
                seq_lens=None, block_tables=None, span_starts=None,
                norm_weight=None, lora=None):
        from ..incubate.nn.functional import lora_delta

        with region("attn_proj"):
            q, k, v = self._qkv(x, cos, sin, norm_weight, lora)
        with region("attn_core"):
            out, new_cache = self._attend(q, k, v, attn_mask, cache,
                                          seq_lens, block_tables,
                                          span_starts)
        with region("attn_proj"):
            y = self.o_proj(out)
            d = lora_delta(lora, out, "self_attn.o_proj")
            if d is not None:
                y = y + d
        return y if cache is None else (y, new_cache)

    def _qkv(self, x, cos, sin, norm_weight, lora):
        """Projections (+ per-slot LoRA deltas) and RoPE: (q, k, v) as
        (b, s, heads, head_dim)."""
        cfg = self.cfg
        b, s = x.shape[:2]
        roped = False
        # batched multi-LoRA (docs/SERVING.md "Multi-LoRA"): ``lora`` is
        # (per-layer stack pack, per-slot adapter ids).  Deltas inject
        # at the PROJECTION OUTPUTS — pre-RoPE for q/k, which is why the
        # LoRA path never takes the fused norm→qkv→rope kernel (the
        # decoder layer pins norm_weight=None when lora is threaded).
        from ..incubate.nn.functional import lora_delta

        if norm_weight is not None:
            # fused RMSNorm→QKV→RoPE (docs/KERNELS.md): ``x`` is the
            # UN-NORMED residual stream — the decoder layer skipped its
            # input_layernorm and handed us its weight, so the fused op
            # reads the hidden states from HBM exactly once.  cos/sin
            # arrive (s, d) for the shared-position paths or (b, s, d)
            # for per-slot serving positions; either way the kernel
            # wants per-token (b·s, d) tables.
            from ..incubate.nn.functional import fused_rms_rope_qkv
            hd = cfg.head_dim
            if cos.ndim == 2:
                cos2 = jnp.broadcast_to(cos[None], (b, s, hd))
                sin2 = jnp.broadcast_to(sin[None], (b, s, hd))
            else:
                cos2, sin2 = cos, sin
            q, k, v = fused_rms_rope_qkv(
                x.reshape(b * s, cfg.hidden_size), norm_weight,
                self.q_proj.weight, self.k_proj.weight,
                self.v_proj.weight, cos2.reshape(b * s, hd),
                sin2.reshape(b * s, hd), hd, cfg.rms_norm_eps)
            q = q.reshape(b, s, cfg.num_attention_heads, hd)
            k = k.reshape(b, s, cfg.num_key_value_heads, hd)
            v = v.reshape(b, s, cfg.num_key_value_heads, hd)
            roped = True
        else:
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            if lora is not None:
                # per-slot adapter deltas on the projection outputs
                # (pre-RoPE, pre-reshape — exactly where a merged
                # W + B_k A_k weight would land them); slot 0 rows add
                # an exact 0.0, keeping base requests bitwise unchanged
                dq = lora_delta(lora, x, "self_attn.q_proj")
                dk = lora_delta(lora, x, "self_attn.k_proj")
                dv = lora_delta(lora, x, "self_attn.v_proj")
                q = q if dq is None else q + dq
                k = k if dk is None else k + dk
                v = v if dv is None else v + dv
            q = q.reshape(b, s, cfg.num_attention_heads, cfg.head_dim)
            k = k.reshape(b, s, cfg.num_key_value_heads, cfg.head_dim)
            v = v.reshape(b, s, cfg.num_key_value_heads, cfg.head_dim)
        # heads are mp-sharded (they came from column-parallel projections)
        q = constrain(q, ("dp", "sharding"), None, "mp", None)
        k = constrain(k, ("dp", "sharding"), None, "mp", None)
        v = constrain(v, ("dp", "sharding"), None, "mp", None)
        if not roped:
            q, k = F.apply_rotary_pos_emb(q, k, cos, sin)
        return q, k, v

    def _attend(self, q, k, v, attn_mask, cache, seq_lens, block_tables,
                span_starts):
        """The attention core, whichever kernel serves it: (out as
        (b, s, heads * head_dim), the new cache or None)."""
        cfg = self.cfg
        b, s = q.shape[:2]
        if cache is not None and block_tables is not None:
            # paged KV pools (serving.Engine): the cache is the GLOBAL
            # (num_blocks, page, H_kv, D) pool pair (or int8 4-tuple),
            # addressed through this batch's block tables.  The unified
            # ragged step: each slot's span (prefill chunk or decode
            # token) writes at [start, start+len) and every row attends
            # its causal prefix — one dispatch for the whole mixed batch
            from ..incubate.nn.functional import ragged_paged_attend
            out, new_cache = ragged_paged_attend(
                cache, q, k, v, block_tables, span_starts, seq_lens)
            out = out.reshape(b, s, cfg.num_attention_heads * cfg.head_dim)
            return out, new_cache
        if cache is not None and s == 1 and seq_lens is not None:
            # single-token decode against the dense KV cache (2-tuple fp
            # or int8-quantized 4-tuple) — shared cache-arity dispatch
            from ..incubate.nn.functional import decode_attend_cache
            out, new_cache = decode_attend_cache(
                cache, q[:, 0], k[:, 0], v[:, 0], seq_lens)
            out = out[:, None].reshape(b, s,
                                       cfg.num_attention_heads * cfg.head_dim)
            return out, new_cache
        if cache is not None:
            # single-shot prefill: causal attention over the prompt, cache
            # written at [0, s) (chunked prefill lives in incubate's
            # FusedMultiTransformer; generate() prefills in one chunk)
            from ..incubate.nn.functional import prefill_write_cache
            new_cache = prefill_write_cache(cache, k, v)
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            out = out.reshape(b, s, cfg.num_attention_heads * cfg.head_dim)
            return out, new_cache
        if cfg.context_parallel and attn_mask is None:
            from ..distributed import cp
            q = cp.split_sequence(q)
            k = cp.split_sequence(k)
            v = cp.split_sequence(v)
            out = cp.context_parallel_attention(q, k, v, causal=True,
                                                impl=cfg.context_parallel)
        else:
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=attn_mask is None)
        out = out.reshape(b, s, cfg.num_attention_heads * cfg.head_dim)
        return out, None


class LlamaMLP(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h, i = cfg.hidden_size, cfg.intermediate_size
        attr = _weight_attr(cfg)
        sp = cfg.sequence_parallel
        self.gate_proj = ColumnParallelLinear(h, i, has_bias=False,
                                              weight_attr=attr, sequence_parallel=sp)
        self.up_proj = ColumnParallelLinear(h, i, has_bias=False,
                                            weight_attr=attr, sequence_parallel=sp)
        self.down_proj = RowParallelLinear(i, h, has_bias=False,
                                           weight_attr=attr, sequence_parallel=sp)

    def forward(self, x, lora=None, live=None):
        """``live`` is the ragged serving step's ``live_token_order``:
        the fused kernel then computes the live lanes only and returns
        zeros on the others; every other path computes every lane."""
        # the region lives here so that every user of the block (a MoE
        # layer's experts too) carries it
        with region("mlp"):
            return self._forward(x, lora, live)

    def _forward(self, x, lora, live):
        cfg = self.cfg
        from ..ops.tuning import geom_key

        if lora is not None:
            # multi-LoRA serving: the gate/up deltas need x and the down
            # delta needs the swiglu intermediate, so the LoRA engine
            # pins the UNFUSED composition (the one-pass fused kernel
            # never materializes that intermediate) — the added fusion
            # here is the grouped BGMV itself
            from ..incubate.nn.functional import lora_delta

            g, u = self.gate_proj(x), self.up_proj(x)
            dg = lora_delta(lora, x, "mlp.gate_proj")
            du = lora_delta(lora, x, "mlp.up_proj")
            g = g if dg is None else g + dg
            u = u if du is None else u + du
            h = F.swiglu(g, u)
            y = self.down_proj(h)
            dd = lora_delta(lora, h, "mlp.down_proj")
            return y if dd is None else y + dd

        def _kernel_serves():
            from ..ops.pallas import fused_mlp as _fm
            return _fm.supported(x.reshape(-1, cfg.hidden_size),
                                 self.gate_proj.weight,
                                 self.down_proj.weight)

        if _use_fused(cfg, "fused_swiglu_mlp",
                      geom_key(h=cfg.hidden_size,
                               i=cfg.intermediate_size),
                      probe=_kernel_serves,
                      layers=(self.gate_proj, self.up_proj,
                              self.down_proj)):
            # one pass over the weights, the (T, I) gate/up intermediate
            # stays in VMEM on TPU (incubate fused entry; XLA
            # composition where the kernel cannot serve)
            from ..incubate.nn import functional as IF
            lead = x.shape[:-1]
            args = (x.reshape(-1, cfg.hidden_size), self.gate_proj.weight,
                    self.up_proj.weight, self.down_proj.weight)
            y = IF.fused_swiglu_mlp(*args) if live is None \
                else IF.fused_swiglu_mlp_live(*args, live)
            return y.reshape(*lead, cfg.hidden_size)
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    returns_aux = False     # MoE variants return (x, aux_loss)
    supports_cache = True   # opt-in flag checked by init_cache/generate
    supports_paged = True   # paged-pool serving path (serving.Engine)

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = LlamaRMSNorm(cfg)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg)
        self.mlp = LlamaMLP(cfg)

    def _attn_input(self, x):
        """(attention input, norm_weight kwarg): under the fused qkv op
        the layernorm folds INTO the attention projection — hand the raw
        residual stream plus the norm weight down instead of norming
        here (resolved at trace time, ops.tuning)."""
        cfg = self.cfg
        from ..ops.tuning import geom_key
        hd = cfg.head_dim
        key = geom_key(h=cfg.hidden_size,
                       nq=cfg.num_attention_heads * hd,
                       nk=cfg.num_key_value_heads * hd, hd=hd)
        attn = self.self_attn

        def _kernel_serves():
            from ..ops.pallas import fused_norm_qkv as _fq
            return _fq.supported(x.reshape(-1, cfg.hidden_size),
                                 attn.q_proj.weight, attn.k_proj.weight,
                                 hd)

        if _use_fused(cfg, "fused_rms_rope_qkv", key,
                      probe=_kernel_serves,
                      layers=(attn.q_proj, attn.k_proj, attn.v_proj)):
            return x, self.input_layernorm.weight
        return self.input_layernorm(x), None

    def forward(self, x, cos, sin, attn_mask=None, cache=None,
                seq_lens=None, block_tables=None, span_starts=None,
                lora=None, mlp_live=None):
        # regions (observability/regions.py): each block opens its own;
        # the residual adds sit in their block's region too, since XLA
        # fuses them into its last matmul and a fusion carries its root's
        # path
        if lora is None:
            attn_in, nw = self._attn_input(x)
        else:
            # LoRA deltas inject pre-RoPE at the projection outputs,
            # which the fused norm→qkv→rope single pass cannot
            # expose — the multi-LoRA engine pins the unfused path
            attn_in, nw = self.input_layernorm(x), None
        attn = self.self_attn(attn_in, cos, sin, attn_mask, cache=cache,
                              seq_lens=seq_lens, block_tables=block_tables,
                              span_starts=span_starts, norm_weight=nw,
                              lora=lora)
        if cache is not None:
            attn, cache = attn
        with region("attn_proj"):
            x = x + attn
        h = self.mlp(self.post_attention_layernorm(x), lora=lora,
                     live=mlp_live)
        with region("mlp"):
            x = x + h
        return x if cache is None else (x, cache)


class LlamaModel(Layer):
    decoder_layer_cls: type = None  # set below; subclasses override

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        cls = type(self).decoder_layer_cls
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        from ..nn.layers_common import LayerList
        if cfg.recompute_num_layers is not None and not (
                0 < cfg.recompute_num_layers <= cfg.num_hidden_layers):
            raise ValueError(
                f"recompute_num_layers={cfg.recompute_num_layers} must be in "
                f"[1, num_hidden_layers={cfg.num_hidden_layers}]")
        if cfg.recompute_num_layers is not None and not cfg.use_recompute \
                and cfg.pipeline_stages <= 1:
            # ADVICE r5: the partial-remat count only takes effect under
            # use_recompute=True — say so instead of silently ignoring it
            # (under pipeline the combination is rejected outright below)
            warnings.warn(
                f"recompute_num_layers={cfg.recompute_num_layers} is "
                "ignored because use_recompute=False — set "
                "use_recompute=True to remat the first N layers",
                UserWarning, stacklevel=2)
        if cfg.pipeline_stages > 1:
            if cfg.recompute_num_layers is not None:
                raise NotImplementedError(
                    "recompute_num_layers applies per stacked layer; the "
                    "pp-scanned body remats uniformly — drop "
                    "recompute_num_layers under pipeline_stages > 1")
            # pipeline-parallel body: per-layer params stacked and sharded
            # over the pp mesh axis (distributed/pipeline.py)
            from ..distributed.pipeline import StackedPipelineStages
            self.layers = StackedPipelineStages(
                lambda: cls(cfg), cfg.num_hidden_layers,
                num_stages=cfg.pipeline_stages,
                num_microbatches=cfg.num_microbatches,
                num_virtual_pipeline_stages=cfg.virtual_pp_degree,
                use_recompute=cfg.use_recompute,
                recompute_policy=cfg.recompute_policy,
                extra_is_batched=(False, False, True),
                has_aux=getattr(cls, "returns_aux", False))
        else:
            layers = []
            for i in range(cfg.num_hidden_layers):
                layer = cls(cfg)
                # partial remat (Megatron's --recompute-num-layers): the
                # non-rematted tail keeps its activations, trading leftover
                # HBM for recompute FLOPs layer by layer
                if cfg.use_recompute and (cfg.recompute_num_layers is None
                                          or i < cfg.recompute_num_layers):
                    layer = RecomputeWrapper(layer, policy=cfg.recompute_policy)
                layers.append(layer)
            self.layers = LayerList(layers)
        self.norm = LlamaRMSNorm(cfg)

    def init_cache(self, batch, max_len, dtype=None):
        """Per-layer dense (k, v) caches for cached generation; dtype
        defaults to the config dtype (bf16 configs get bf16 caches)."""
        cfg = self.cfg
        if cfg.pipeline_stages > 1:
            raise NotImplementedError(
                "cached generation requires pipeline_stages == 1")
        if not getattr(type(self).decoder_layer_cls, "supports_cache",
                       False):
            raise NotImplementedError(
                f"{type(self).decoder_layer_cls.__name__} does not support "
                "KV caches (generate() falls back to full recompute)")
        from .generation import make_dense_caches
        return make_dense_caches(
            cfg.num_hidden_layers, batch, max_len,
            cfg.num_key_value_heads, cfg.head_dim,
            dtype if dtype is not None else cfg.dtype)

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                caches=None, seq_lens=None, block_tables=None,
                span_starts=None, lora=None):
        cfg = self.cfg
        if caches is not None:
            if attn_mask is not None or position_ids is not None:
                raise NotImplementedError(
                    "cached forward supports dense causal prefill/decode "
                    "only — attn_mask/position_ids would be silently "
                    "ignored (left-pad or trim prompts instead)")
            return self._forward_cached(input_ids, caches, seq_lens,
                                        block_tables, span_starts, lora)
        with region("embed"):
            x = self.embed_tokens(input_ids)
        with region("attn_proj"):
            cos, sin = F.rope_cos_sin(input_ids.shape[1], cfg.head_dim,
                                      base=cfg.rope_theta, dtype=x.dtype,
                                      position_ids=position_ids)
        aux = 0.0
        if cfg.pipeline_stages > 1:
            x = self.layers(x, cos, sin, attn_mask)
            if isinstance(x, tuple):
                x, aux = x
        else:
            for layer in self.layers:
                x = layer(x, cos, sin, attn_mask)
                if isinstance(x, tuple):
                    x, a = x
                    aux = aux + a
        # same-trace stash consumed by the CausalLM head (no transform
        # boundary between model and head, so this is legal under jit)
        self.__dict__["_moe_aux"] = aux
        return self.norm(x)

    def _forward_cached(self, input_ids, caches, seq_lens,
                        block_tables=None, span_starts=None, lora=None):
        """Prefill (seq_lens None) or one-token decode against the caches.
        With ``block_tables`` the caches are paged pools (serving path):
        prefill also takes ``seq_lens`` as the real prompt lengths so
        padding never lands in the pool.  With ``span_starts`` the batch
        is the unified RAGGED serving step: per-slot spans (chunked
        prefill or decode tokens) at positions ``[start, start+len)``,
        ``seq_lens`` carrying the span lengths.  ``lora`` is the
        multi-LoRA pair (per-layer stacked adapter packs, per-slot
        adapter ids) — each decoder layer consumes its own pack.
        Returns (hidden, new_caches)."""
        cfg = self.cfg
        with region("embed"):
            x = self.embed_tokens(input_ids)
        b, s = input_ids.shape
        decode = (s == 1 and seq_lens is not None)
        with region("attn_proj"):
            if span_starts is not None:
                # per-slot positions: the span's tokens sit at
                # start..start+s
                cos, sin = F.rope_cos_sin(
                    s, cfg.head_dim, base=cfg.rope_theta, dtype=x.dtype,
                    position_ids=span_starts[:, None]
                    + jnp.arange(s)[None, :])
            elif decode:
                cos, sin = F.rope_cos_sin(
                    1, cfg.head_dim, base=cfg.rope_theta, dtype=x.dtype,
                    position_ids=seq_lens[:, None])
            else:
                cos, sin = F.rope_cos_sin(s, cfg.head_dim,
                                          base=cfg.rope_theta, dtype=x.dtype)
        # the paged kwargs are only threaded when present: decoder-layer
        # subclasses without paged support (MoE) keep their signature
        kw = {} if block_tables is None else {"block_tables": block_tables}
        if span_starts is not None:
            kw["span_starts"] = span_starts
            # the step's live lanes, once a step and not once a layer:
            # the MLP's cost follows them (ops/pallas/fused_mlp.py)
            from ..incubate.nn.functional import live_token_order
            kw["mlp_live"] = live_token_order(seq_lens, s)
        lens_arg = seq_lens if (decode or block_tables is not None) \
            else None
        # per-layer LoRA packs: run_cached_layers walks the stack in
        # order, so a sequential iterator hands each layer its own pack
        # at trace time (adapter ids are shared batch data)
        lit = iter(lora[0]) if lora is not None else None
        laids = lora[1] if lora is not None else None
        from .generation import run_cached_layers
        x, new_caches = run_cached_layers(
            self.layers, x, caches,
            lambda inner, x, cache: inner(
                x, cos, sin, cache=cache, seq_lens=lens_arg,
                # threaded only when present, like the paged kwargs (MoE
                # layers keep their signature)
                **({} if lit is None else {"lora": (next(lit), laids)}),
                **kw))
        self.__dict__["_moe_aux"] = 0.0
        return self.norm(x), new_caches


class LlamaForCausalLM(CachedGenerationMixin, Layer):
    model_cls: type = None  # set below; subclasses override

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = type(self).model_cls(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size,
                                                has_bias=False,
                                                weight_attr=_weight_attr(cfg))
        self.loss_fn = ParallelCrossEntropy(ignore_index=-100)

    def logits(self, hidden):
        with region("lm_head_loss"):
            if self.cfg.tie_word_embeddings:
                # (vocab, hidden), mp on vocab
                w = self.model.embed_tokens.weight
                logits = hidden @ w.T
                return constrain(logits, ("dp", "sharding"), None, "mp")
            return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, attn_mask=None, position_ids=None):
        hidden = self.model(input_ids, attn_mask, position_ids)
        if labels is None:
            return self.logits(hidden)
        chunks = self.cfg.loss_seq_chunks
        if chunks > 1:
            if hidden.shape[1] % chunks == 0:
                return self._chunked_loss(hidden, labels, chunks)
            import warnings
            warnings.warn(
                f"loss_seq_chunks={chunks} does not divide seq_len="
                f"{hidden.shape[1]}; falling back to the monolithic "
                "[B,S,V] logits path (full logits WILL be materialized)",
                stacklevel=2)
        logits = self.logits(hidden)
        with region("lm_head_loss"):
            loss = self.loss_fn(logits.astype(jnp.float32), labels)
            valid = (labels != -100)
            return jnp.sum(loss * valid) / jnp.maximum(jnp.sum(valid), 1)

    def _chunked_loss(self, hidden, labels, chunks):
        """Memory-efficient vocab CE: the [B,S,V] logits tensor (the
        single largest activation — ~1 GiB fp32 at bs4/seq2048/32k vocab)
        is never materialized. Each sequence chunk's logits are computed,
        reduced to a loss sum, and rematerialized in the backward pass
        (one extra lm_head matmul, ~3% of step FLOPs, for a ~2-3 GiB HBM
        highwater cut that buys a larger batch). Chunking is along the
        sequence axis so vocab-parallel (mp) sharding is untouched."""
        s_chunk = hidden.shape[1] // chunks

        @jax.checkpoint
        def chunk_sums(h, l):
            logits = self.logits(h)
            loss = self.loss_fn(logits.astype(jnp.float32), l)
            valid = (l != -100)
            return jnp.sum(loss * valid), jnp.sum(valid)

        with region("lm_head_loss"):
            total = jnp.float32(0.0)
            count = jnp.int32(0)
            for c in range(chunks):  # unrolled: XLA overlaps chunks
                sl = slice(c * s_chunk, (c + 1) * s_chunk)
                s, n = chunk_sums(hidden[:, sl], labels[:, sl])
                total += s
                count += n
            return total / jnp.maximum(count, 1)

    def _cache_supported(self) -> bool:
        return (self.cfg.pipeline_stages == 1
                and getattr(type(self.model).decoder_layer_cls,
                            "supports_cache", False))


LlamaModel.decoder_layer_cls = LlamaDecoderLayer
LlamaForCausalLM.model_cls = LlamaModel


def llama(name_or_config="tiny", **overrides) -> LlamaForCausalLM:
    cfg = (PRESETS[name_or_config] if isinstance(name_or_config, str)
           else name_or_config)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return LlamaForCausalLM(cfg)


def causal_lm_loss(model, batch):
    """Standard loss_fn for TrainStep."""
    return model(batch["input_ids"], labels=batch["labels"])
