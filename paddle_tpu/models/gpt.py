"""GPT / ERNIE-style decoder family (BASELINE.json configs[1]: 13B TP+PP).

Reference capability: PaddleNLP's GPT-3 / ERNIE models trained with fleet
hybrid parallel on the reference core (SURVEY §0 scope note; fleet layers
§2.5). Differences from the Llama family that make this a distinct
architecture (matching the GPT/ERNIE lineage): learned absolute position
embeddings (no RoPE), full multi-head attention (no GQA), LayerNorm (not
RMSNorm) with biases, GELU 4h FFN, optional embedding dropout.

TPU-first: same mesh-axis design as llama.py — ColumnParallel/RowParallel
("mp"), Megatron-SP, pipeline stages via StackedPipelineStages ("pp"),
recompute, vocab-parallel CE — all inside one jit program.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, ParamAttr
from ..observability.regions import region
from ..nn.layers_common import Dropout, Embedding, LayerList, LayerNorm
from ..distributed.mp_layers import (ColumnParallelLinear,
                                     ParallelCrossEntropy,
                                     RowParallelLinear,
                                     VocabParallelEmbedding, constrain)
from ..distributed.recompute import RecomputeWrapper
from .generation import CachedGenerationMixin


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    intermediate_size: Optional[int] = None      # default 4h
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_recompute: bool = False
    recompute_policy: Optional[str] = None
    recompute_num_layers: Optional[int] = None  # Megatron-style partial remat
    sequence_parallel: bool = False
    pipeline_stages: int = 1
    num_microbatches: Optional[int] = None
    virtual_pp_degree: int = 1
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size


PRESETS = {
    # GPT-3 ladder (PaddleNLP gpt3 configs)
    "gpt2-345m": GPTConfig(),
    "gpt3-1.3b": GPTConfig(hidden_size=2048, num_hidden_layers=24,
                           num_attention_heads=32,
                           max_position_embeddings=2048),
    "gpt3-6.7b": GPTConfig(hidden_size=4096, num_hidden_layers=32,
                           num_attention_heads=32,
                           max_position_embeddings=2048),
    # BASELINE configs[1]: 13B decoder for TP+PP
    "gpt3-13b": GPTConfig(hidden_size=5120, num_hidden_layers=40,
                          num_attention_heads=40,
                          max_position_embeddings=2048),
    # ERNIE-style base (ernie-3.0 dense decoder shape)
    "ernie-base": GPTConfig(vocab_size=40000, hidden_size=768,
                            num_hidden_layers=12, num_attention_heads=12,
                            max_position_embeddings=2048),
    "tiny": GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, max_position_embeddings=128),
}


def _attr(cfg: GPTConfig) -> ParamAttr:
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        sp = cfg.sequence_parallel
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, has_bias=True,
                                             weight_attr=_attr(cfg),
                                             sequence_parallel=sp)
        self.out_proj = RowParallelLinear(h, h, has_bias=True,
                                          weight_attr=_attr(cfg),
                                          sequence_parallel=sp)
        self.dropout = Dropout(cfg.hidden_dropout)

    def forward(self, x, attn_mask=None, cache=None, seq_lens=None,
                block_tables=None, span_starts=None, lora=None):
        cfg = self.cfg
        b, s = x.shape[:2]
        # multi-LoRA serving (docs/SERVING.md "Multi-LoRA"): per-slot
        # adapter deltas on the packed qkv projection and on out_proj —
        # x here is already ln_1-normed, exactly the projections' input
        from ..incubate.nn.functional import lora_delta

        with region("attn_proj"):
            qkv = self.qkv_proj(x)
            dqkv = lora_delta(lora, x, "attn.qkv_proj")
            if dqkv is not None:
                qkv = qkv + dqkv
            qkv = qkv.reshape(b, s, 3, cfg.num_attention_heads,
                              cfg.head_dim)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            q = constrain(q, ("dp", "sharding"), None, "mp", None)
            k = constrain(k, ("dp", "sharding"), None, "mp", None)
            v = constrain(v, ("dp", "sharding"), None, "mp", None)
        with region("attn_core"):
            out, new_cache = self._attend(q, k, v, attn_mask, cache,
                                          seq_lens, block_tables,
                                          span_starts)
        with region("attn_proj"):
            y = self.out_proj(out)
            d = lora_delta(lora, out, "attn.out_proj")
            if d is not None:
                y = y + d
            y = self.dropout(y)
        return y if cache is None else (y, new_cache)

    def _attend(self, q, k, v, attn_mask, cache, seq_lens, block_tables,
                span_starts):
        """The attention core, whichever kernel serves it: (out as
        (b, s, hidden), the new cache or None)."""
        cfg = self.cfg
        b, s = q.shape[:2]
        if cache is not None and block_tables is not None:
            # paged KV pools (serving.Engine) — see LlamaAttention
            from ..incubate.nn.functional import ragged_paged_attend
            out, new_cache = ragged_paged_attend(
                cache, q, k, v, block_tables, span_starts, seq_lens)
            out = out.reshape(b, s, cfg.hidden_size)
            return out, new_cache
        if cache is not None and s == 1 and seq_lens is not None:
            # single-token decode against the dense (or int8-quantized
            # 4-tuple) KV cache — shared cache-arity dispatch
            from ..incubate.nn.functional import decode_attend_cache
            out, new_cache = decode_attend_cache(
                cache, q[:, 0], k[:, 0], v[:, 0], seq_lens)
            out = out[:, None].reshape(b, s, cfg.hidden_size)
            return out, new_cache
        if cache is not None:
            from ..incubate.nn.functional import prefill_write_cache
            new_cache = prefill_write_cache(cache, k, v)
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=cfg.attention_dropout, training=self.training)
            out = out.reshape(b, s, cfg.hidden_size)
            return out, new_cache
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
            dropout_p=cfg.attention_dropout, training=self.training)
        out = out.reshape(b, s, cfg.hidden_size)
        return out, None


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        sp = cfg.sequence_parallel
        self.fc_in = ColumnParallelLinear(cfg.hidden_size, cfg.ffn_size,
                                          has_bias=True,
                                          weight_attr=_attr(cfg),
                                          sequence_parallel=sp)
        self.fc_out = RowParallelLinear(cfg.ffn_size, cfg.hidden_size,
                                        has_bias=True,
                                        weight_attr=_attr(cfg),
                                        sequence_parallel=sp)
        self.dropout = Dropout(cfg.hidden_dropout)

    def forward(self, x, lora=None):
        with region("mlp"):
            return self._forward(x, lora)

    def _forward(self, x, lora):
        if lora is not None:
            # multi-LoRA: the fc_out delta needs the GELU intermediate,
            # so the LoRA path pins the unfused FFN composition
            from ..incubate.nn.functional import lora_delta

            h1 = self.fc_in(x)
            d1 = lora_delta(lora, x, "mlp.fc_in")
            if d1 is not None:
                h1 = h1 + d1
            h = F.gelu(h1)
            y = self.fc_out(h)
            d2 = lora_delta(lora, h, "mlp.fc_out")
            return self.dropout(y if d2 is None else y + d2)

        return self.dropout(self.fc_out(F.gelu(self.fc_in(x))))


class GPTDecoderLayer(Layer):
    returns_aux = False
    supports_cache = True
    supports_paged = True   # paged-pool serving path (serving.Engine)

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln_2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.mlp = GPTMLP(cfg)

    def forward(self, x, attn_mask=None, cache=None, seq_lens=None,
                block_tables=None, span_starts=None, lora=None):
        # regions (observability/regions.py): each block opens its own;
        # LayerNorm is the shared nn layer, so its region is opened here,
        # and the residual adds sit in their block's region (XLA fuses
        # them into its last matmul, and a fusion carries its root's path)
        with region("norm"):
            h = self.ln_1(x)
        if cache is not None:
            attn, cache = self.attn(h, attn_mask, cache=cache,
                                    seq_lens=seq_lens,
                                    block_tables=block_tables,
                                    span_starts=span_starts, lora=lora)
        else:
            attn = self.attn(h, attn_mask)
        with region("attn_proj"):
            x = x + attn
        with region("norm"):
            h = self.ln_2(x)
        h = self.mlp(h, lora=lora)
        with region("mlp"):
            x = x + h
        return x if cache is None else (x, cache)


class GPTModel(Layer):
    decoder_layer_cls: type = GPTDecoderLayer

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                   cfg.hidden_size)
        # position table is small → replicated plain embedding (the token
        # table is the one worth vocab-sharding)
        self.embed_positions = Embedding(cfg.max_position_embeddings,
                                         cfg.hidden_size,
                                         weight_attr=_attr(cfg))
        self.embed_dropout = Dropout(cfg.hidden_dropout)
        if cfg.recompute_num_layers is not None and not (
                0 < cfg.recompute_num_layers <= cfg.num_hidden_layers):
            raise ValueError(
                f"recompute_num_layers={cfg.recompute_num_layers} must "
                f"be in [1, num_hidden_layers={cfg.num_hidden_layers}]")
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
            from ..distributed.pipeline import StackedPipelineStages
            self.h = StackedPipelineStages(
                lambda: GPTDecoderLayer(cfg), cfg.num_hidden_layers,
                num_stages=cfg.pipeline_stages,
                num_microbatches=cfg.num_microbatches,
                num_virtual_pipeline_stages=cfg.virtual_pp_degree,
                use_recompute=cfg.use_recompute,
                recompute_policy=cfg.recompute_policy,
                extra_is_batched=(True,),
                has_aux=False)
        else:
            layers = []
            for i in range(cfg.num_hidden_layers):
                layer = GPTDecoderLayer(cfg)
                # partial remat (Megatron --recompute-num-layers): only
                # the first N layers re-run in backward
                if cfg.use_recompute and (
                        cfg.recompute_num_layers is None
                        or i < cfg.recompute_num_layers):
                    layer = RecomputeWrapper(layer,
                                             policy=cfg.recompute_policy)
                layers.append(layer)
            self.h = LayerList(layers)
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def init_cache(self, batch, max_len, dtype=None):
        """Per-layer dense (k, v) caches for cached generation."""
        cfg = self.cfg
        if cfg.pipeline_stages > 1:
            raise NotImplementedError(
                "cached generation requires pipeline_stages == 1")
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_len {max_len} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings} (learned positions)")
        from .generation import make_dense_caches
        return make_dense_caches(
            cfg.num_hidden_layers, batch, max_len,
            cfg.num_attention_heads, cfg.head_dim,
            dtype if dtype is not None else cfg.dtype)

    def _forward_cached(self, input_ids, caches, seq_lens,
                        block_tables=None, span_starts=None, lora=None):
        """Prefill (seq_lens None) or one-token decode against the caches.
        With ``block_tables`` the caches are paged pools (serving path);
        prefill then takes ``seq_lens`` as the real prompt lengths.  With
        ``span_starts`` the batch is the unified RAGGED serving step
        (chunked prefill + decode spans, ``seq_lens`` = span lengths).
        ``lora`` is the multi-LoRA pair (per-layer adapter packs,
        per-slot adapter ids).  Returns (hidden, new_caches)."""
        b, s = input_ids.shape
        decode = (s == 1 and seq_lens is not None)
        with region("embed"):
            if span_starts is not None:
                pos = span_starts[:, None] + jnp.arange(s)[None, :]
            elif decode:
                pos = seq_lens[:, None]
            else:
                pos = jnp.arange(s)[None, :]
            x = self.embed_tokens(input_ids) + self.embed_positions(pos)
            x = self.embed_dropout(x)
        kw = {} if block_tables is None else {"block_tables": block_tables}
        if span_starts is not None:
            kw["span_starts"] = span_starts
        lens_arg = seq_lens if (decode or block_tables is not None) \
            else None
        lit = iter(lora[0]) if lora is not None else None
        laids = lora[1] if lora is not None else None
        from .generation import run_cached_layers
        x, new_caches = run_cached_layers(
            self.h, x, caches,
            lambda inner, x, cache: inner(
                x, cache=cache, seq_lens=lens_arg,
                lora=None if lit is None else (next(lit), laids), **kw))
        with region("norm"):
            return self.ln_f(x), new_caches

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                caches=None, seq_lens=None, block_tables=None,
                span_starts=None, lora=None):
        cfg = self.cfg
        if caches is not None:
            if attn_mask is not None or position_ids is not None:
                raise NotImplementedError(
                    "cached forward supports dense causal prefill/decode "
                    "only — attn_mask/position_ids would be silently "
                    "ignored")
            return self._forward_cached(input_ids, caches, seq_lens,
                                        block_tables, span_starts, lora)
        if input_ids.shape[1] > cfg.max_position_embeddings:
            # learned absolute positions: jax's OOB gather would silently
            # clamp every index past the table to its last row
            raise ValueError(
                f"sequence length {input_ids.shape[1]} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        with region("embed"):
            if position_ids is None:
                position_ids = jnp.arange(input_ids.shape[1])[None, :]
            x = (self.embed_tokens(input_ids)
                 + self.embed_positions(position_ids))
            x = self.embed_dropout(x)
        if cfg.pipeline_stages > 1:
            x = self.h(x, attn_mask)
        else:
            for layer in self.h:
                x = layer(x, attn_mask)
        with region("norm"):
            return self.ln_f(x)


class GPTForCausalLM(CachedGenerationMixin, Layer):
    def _cache_supported(self) -> bool:
        return self.cfg.pipeline_stages == 1

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(cfg.hidden_size,
                                                cfg.vocab_size,
                                                has_bias=False,
                                                weight_attr=_attr(cfg))
        self.loss_fn = ParallelCrossEntropy(ignore_index=-100)

    def logits(self, hidden):
        with region("lm_head_loss"):
            if self.cfg.tie_word_embeddings:
                w = self.model.embed_tokens.weight
                logits = hidden @ w.T
                return constrain(logits, ("dp", "sharding"), None, "mp")
            return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, attn_mask=None,
                position_ids=None):
        hidden = self.model(input_ids, attn_mask, position_ids)
        logits = self.logits(hidden)
        if labels is None:
            return logits
        with region("lm_head_loss"):
            loss = self.loss_fn(logits.astype(jnp.float32), labels)
            valid = (labels != -100)
            return jnp.sum(loss * valid) / jnp.maximum(jnp.sum(valid), 1)

def gpt(name_or_config="tiny", **overrides) -> GPTForCausalLM:
    cfg = (PRESETS[name_or_config] if isinstance(name_or_config, str)
           else name_or_config)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return GPTForCausalLM(cfg)
