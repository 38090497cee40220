"""EvaByte: a byte-level causal LM whose attention is EVA, chunked
linearized attention (Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023; the EvaByte release, hkunlp.github.io/blog/2025/
evabyte).

What differs from the Llama family (``models/llama.py``), which it shares
its MLP (``LlamaMLP``, so ``fused_swiglu_mlp``), its RoPE and its region
vocabulary with:

- **attention.**  A query at position ``t`` lies in window ``w = t // W``.
  It sees the exact keys of its own window up to itself and ONE summary
  ``(ktilde_j, vtilde_j)`` for every chunk ``j`` of ``c`` positions of
  every EARLIER window, under one softmax.  A chunk's summary is a
  softmax-weighted mean of its keys (weights ``softmax(s * phi_h . k)``
  with a learned per-head ``phi_h``) plus a learned per-head ``mu_h``, and
  the same weights' mean of its values.  So a request's cache stops
  growing by a key and a value per position once a window closes: it
  holds ``W / c`` exact pages of the open window and one summary row per
  completed chunk (``serving/block_allocator.py``:
  ``WindowSummarySpec``);
- RMSNorm adds 1 to its weight (``norm_add_unit_offset``); the residual
  stream is float32 (``fp32_skip_add``); the head is float32
  (``fp32_logits``) and ``num_pred_heads * vocab`` wide: columns
  ``vocab * i ..`` are prediction head ``i``, which predicts byte
  ``t + 1 + i``.  :meth:`EvaByteForCausalLM.logits` gives head 0 (what a
  sampler decodes from), :meth:`EvaByteForCausalLM.all_heads_logits` all
  of them.

Two forwards: the UNCACHED one in plain XLA ops (training-shaped calls,
``generate()``, which recomputes the prefix, and the CPU tests) and the
PAGED one ``serving.Engine`` calls (``incubate.nn.functional.
eva_paged_attend``: span write, chunk summaries, one ragged attention
over summary pages then window pages).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..observability.regions import region
from ..distributed.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                     VocabParallelEmbedding)
from .generation import CachedGenerationMixin, run_cached_layers
from .llama import LlamaMLP, _weight_attr

__all__ = ["EvaByteConfig", "EvaByteForCausalLM", "EvaByteModel", "PRESETS",
           "evabyte"]


@dataclasses.dataclass
class EvaByteConfig:
    vocab_size: int = 320               # 256 bytes + 64 specials
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_pred_heads: int = 8
    max_position_embeddings: int = 32768
    window_size: int = 2048
    chunk_size: int = 16
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    initializer_range: float = 0.02
    fused_ops: str = "auto"             # LlamaMLP's (docs/KERNELS.md)
    dtype: str = "float32"
    # what LlamaMLP and serving.Engine read off a causal LM's config
    sequence_parallel: bool = False
    pipeline_stages: int = 1
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size={self.window_size} must be a multiple of "
                f"chunk_size={self.chunk_size}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_key_value_heads(self) -> int:
        return self.num_attention_heads         # MHA, no grouping


PRESETS = {
    "evabyte-6.5b": EvaByteConfig(),
    "tiny": EvaByteConfig(hidden_size=64, intermediate_size=128,
                          num_hidden_layers=2, num_attention_heads=4,
                          max_position_embeddings=512, window_size=64),
}


class EvaByteRMSNorm(Layer):
    """RMSNorm whose stored weight is the offset from 1
    (``norm_add_unit_offset``)."""

    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.eps = cfg.rms_norm_eps
        self.weight = self.create_parameter(
            (cfg.hidden_size,), default_initializer=I.Constant(0.0))

    def forward(self, x):
        with region("norm"):
            return F.rms_norm(x, 1.0 + self.weight.astype(jnp.float32),
                              self.eps)


def eva_attention(q, k, v, phi, mu, window: int, chunk: int):
    """The uncached attention in plain XLA ops: q, k, v ``(B, S, H, D)``
    after RoPE, every sequence from position 0.  One ``(S, S + S / c)``
    block of scores a head: a training-shaped or test-sized call, not the
    serving path.  Returns ``(B, S, H, D)``."""
    from ..incubate.nn.functional import _prec, eva_chunk_summaries
    b, s, h, d = q.shape
    scale = d ** -0.5
    p = _prec(q.dtype)
    pad = -s % chunk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (s + pad) // chunk
    with jax.named_scope("eva_summarise"):
        kt, vt = eva_chunk_summaries(kp.reshape(b, nc, chunk, h, d),
                                     vp.reshape(b, nc, chunk, h, d), phi,
                                     mu, scale)             # (B, nc, H, D)
    with jax.named_scope("eva_attend"):
        qf = q.astype(jnp.float32)
        t = jnp.arange(s)
        sc_w = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32),
                          precision=p) * scale
        own = (t[None, :] <= t[:, None]) \
            & (t[None, :] // window == t[:, None] // window)
        sc_w = jnp.where(own[None, None], sc_w, -jnp.inf)
        sc_s = jnp.einsum("bqhd,bjhd->bhqj", qf, kt, precision=p) * scale
        # a summary is seen from the windows after its own: a trailing
        # ragged chunk lies in the last window and is seen by nobody
        seen = jnp.arange(nc)[None, :] < (window // chunk) \
            * (t[:, None] // window)
        sc_s = jnp.where(seen[None, None], sc_s, -jnp.inf)
        pr = jax.nn.softmax(jnp.concatenate([sc_w, sc_s], axis=-1), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", pr[..., :s],
                         v.astype(jnp.float32), precision=p) \
            + jnp.einsum("bhqj,bjhd->bqhd", pr[..., s:], vt, precision=p)
    return out.astype(q.dtype)


class EvaByteAttention(Layer):
    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.cfg = cfg
        h, nh, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        attr = _weight_attr(cfg)
        self.q_proj = ColumnParallelLinear(h, nh * hd, has_bias=False,
                                           weight_attr=attr)
        self.k_proj = ColumnParallelLinear(h, nh * hd, has_bias=False,
                                           weight_attr=attr)
        self.v_proj = ColumnParallelLinear(h, nh * hd, has_bias=False,
                                           weight_attr=attr)
        self.o_proj = RowParallelLinear(nh * hd, h, has_bias=False,
                                        weight_attr=attr)
        # the summariser's two learned per-head vectors (released names)
        self.adaptive_phi = self.create_parameter((nh, hd), attr=attr)
        self.adaptive_mu_k = self.create_parameter((nh, hd), attr=attr)

    def forward(self, x, cos, sin, cache=None, seq_lens=None,
                block_tables=None, cache_aux=None):
        cfg = self.cfg
        b, s = x.shape[:2]
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        with region("attn_proj"):
            q = self.q_proj(x).reshape(b, s, nh, hd)
            k = self.k_proj(x).reshape(b, s, nh, hd)
            v = self.v_proj(x).reshape(b, s, nh, hd)
            q, k = F.apply_rotary_pos_emb(q, k, cos, sin)
        with region("attn_core"):
            if cache is None:
                out, new_cache = eva_attention(
                    q, k, v, self.adaptive_phi, self.adaptive_mu_k,
                    cfg.window_size, cfg.chunk_size), None
            else:
                from ..incubate.nn.functional import eva_paged_attend
                out, new_cache = eva_paged_attend(
                    cache, q, k, v, block_tables, seq_lens, cache_aux,
                    self.adaptive_phi, self.adaptive_mu_k)
        with region("attn_proj"):
            y = self.o_proj(out.reshape(b, s, nh * hd))
        return y, new_cache


class EvaByteDecoderLayer(Layer):
    supports_cache = False  # no dense (B, S_max) cache: generate() recomputes
    supports_paged = True   # paged-pool serving path (serving.Engine)

    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = EvaByteRMSNorm(cfg)
        self.self_attn = EvaByteAttention(cfg)
        self.post_attention_layernorm = EvaByteRMSNorm(cfg)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cos, sin, cache=None, seq_lens=None,
                block_tables=None, cache_aux=None, mlp_live=None):
        """``x`` is the float32 residual stream (``fp32_skip_add``); the
        blocks compute in the parameters' dtype."""
        dt = self.self_attn.q_proj.weight.dtype
        attn, cache = self.self_attn(
            self.input_layernorm(x).astype(dt), cos, sin, cache=cache,
            seq_lens=seq_lens, block_tables=block_tables,
            cache_aux=cache_aux)
        with region("attn_proj"):
            x = x + attn.astype(jnp.float32)
        h = self.mlp(self.post_attention_layernorm(x).astype(dt),
                     live=mlp_live)
        with region("mlp"):
            x = x + h.astype(jnp.float32)
        return x, cache


class EvaByteModel(Layer):
    decoder_layer_cls = EvaByteDecoderLayer

    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                   cfg.hidden_size)
        from ..nn.layers_common import LayerList
        self.layers = LayerList([EvaByteDecoderLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = EvaByteRMSNorm(cfg)

    def _rope(self, positions):
        # float32 tables whatever the parameters' dtype: q and k are
        # rotated in float32 and rounded once (``mixedp_attn``)
        return F.rope_cos_sin(positions.shape[-1], self.cfg.head_dim,
                              base=self.cfg.rope_theta, dtype=jnp.float32,
                              position_ids=positions)

    def forward(self, input_ids, caches=None, seq_lens=None,
                block_tables=None, span_starts=None, cache_aux=None):
        """Uncached: ``input_ids`` ``(B, S)`` from position 0, returns the
        normed hidden states ``(B, S, h)`` in float32.  Paged
        (``caches`` = the engine's pools): the ragged step's spans at
        ``span_starts`` (RoPE's positions), ``seq_lens`` their lengths,
        ``block_tables`` each row's summary pages then window pages and
        ``cache_aux`` what ``WindowSummarySpec.step_aux`` made for them;
        returns ``(hidden, new_caches)``."""
        with region("embed"):
            x = self.embed_tokens(input_ids).astype(jnp.float32)
        b, s = input_ids.shape
        with region("attn_proj"):
            if caches is None:
                cos, sin = self._rope(jnp.arange(s))
            else:
                cos, sin = self._rope(span_starts[:, None]
                                      + jnp.arange(s)[None, :])
        if caches is None:
            for layer in self.layers:
                x, _ = layer(x, cos, sin)
            return self.norm(x)
        # the step's live lanes, once a step and not once a layer: the
        # MLP's cost follows them (ops/pallas/fused_mlp.py)
        from ..incubate.nn.functional import live_token_order
        live = live_token_order(seq_lens, s)
        x, new_caches = run_cached_layers(
            self.layers, x, caches,
            lambda inner, x, cache: inner(
                x, cos, sin, cache=cache, seq_lens=seq_lens,
                block_tables=block_tables, cache_aux=cache_aux,
                mlp_live=live))
        return self.norm(x), new_caches


class EvaByteForCausalLM(CachedGenerationMixin, Layer):
    def __init__(self, cfg: EvaByteConfig):
        super().__init__()
        self.cfg = cfg
        self.model = EvaByteModel(cfg)
        self.lm_head = ColumnParallelLinear(
            cfg.hidden_size, cfg.num_pred_heads * cfg.vocab_size,
            has_bias=False, weight_attr=_weight_attr(cfg))

    def kv_cache_spec(self) -> dict:
        """What ``serving.Engine`` reads where it reads a Llama's
        ``(layers, kv_heads, head_dim)``: the cache kind and its sizes
        (``serving/block_allocator.py`` ``cache_spec_of``)."""
        cfg = self.cfg
        return {"kind": "window+summary", "layers": cfg.num_hidden_layers,
                "kv_heads": cfg.num_attention_heads,
                "head_dim": cfg.head_dim, "window": cfg.window_size,
                "chunk": cfg.chunk_size}

    def all_heads_logits(self, hidden):
        """``(..., num_pred_heads, vocab)`` in float32 (``fp32_logits``):
        head ``i`` predicts byte ``t + 1 + i``."""
        cfg = self.cfg
        with region("lm_head_loss"):
            lg = jnp.matmul(hidden.astype(jnp.float32),
                            self.lm_head.weight.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
            return lg.reshape(*hidden.shape[:-1], cfg.num_pred_heads,
                              cfg.vocab_size)

    def logits(self, hidden):
        """Head 0's ``(..., vocab)`` logits: what plain decoding samples
        from."""
        cfg = self.cfg
        with region("lm_head_loss"):
            return jnp.matmul(
                hidden.astype(jnp.float32),
                self.lm_head.weight[:, :cfg.vocab_size].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)

    def forward(self, input_ids):
        """Head 0's logits ``(B, S, vocab)`` of the uncached forward."""
        return self.logits(self.model(input_ids))


def evabyte(name_or_config="tiny", **overrides) -> EvaByteForCausalLM:
    cfg = (PRESETS[name_or_config] if isinstance(name_or_config, str)
           else name_or_config)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return EvaByteForCausalLM(cfg)
