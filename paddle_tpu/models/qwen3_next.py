"""Qwen3-Next (Qwen3-Next-80B-A3B): a causal LM whose layers are of two
kinds and whose feed-forward block is sparse.

- **Layers.**  Layer ``i`` (0-based) is *full attention* when
  ``(i + 1) % full_attention_interval == 0`` and a *gated delta* layer
  (Gated DeltaNet) otherwise: three linear-attention layers, whose state
  is a ``(dk, dv)`` matrix a head, to every softmax-attention layer.
  ``a = x + Mixer(Norm(x))``; ``out = a + Sparse(Norm(a))``.
- **Norm.**  RMSNorm whose stored weight is the offset from 1
  (``RMS(x) * (1 + w)``), everywhere but inside the gated-delta mixer.
- **Full-attention mixer.**  ``q_proj`` gives per head a query and an
  output gate (``[q D | gate D]``); q and k are normalised per head
  (zero-centred weights of ``D``); RoPE turns the first
  ``partial_rotary_factor * D`` dimensions only; grouped-query causal
  softmax attention with a head size that is a key of the config
  (256 at hidden 2,048: not ``hidden / heads``);
  ``y = o_proj(attn * sigmoid(gate))``.
- **Gated-delta mixer.**  ``in_proj_qkvz`` is laid out per key head
  ``[q dk | k dk | v r dv | z r dv]`` and ``in_proj_ba`` ``[b r | a r]``
  (``r`` value heads a key head); a depthwise causal conv of
  ``linear_conv_kernel_dim`` with SiLU over the concatenated q, k, v;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q and
  k L2-normalised; the gated delta rule in its chunked form
  (``incubate.nn.functional.gated_delta_rule``: on TPU the Pallas kernel
  pair of ``ops/pallas/gated_delta.py``, which reads key head ``h // r``
  for value head ``h`` itself, so q and k go in unrepeated, and which
  keeps what its backward needs, so the rule is under no
  ``jax.checkpoint`` here; elsewhere an XLA composition that carries its
  own); per head ``(w * RMS(o)) * silu(z)`` (this one norm is plain);
  ``out_proj``.
- **Sparse block.**  ``distributed.moe.DroplessMoE``: softmax over all
  ``num_experts``, top ``num_experts_per_tok`` renormalised, the experts
  held here (``experts_held``) through one grouped product with no
  capacity and no dropped token, and a shared expert behind a sigmoid
  gate.

The uncached forward trains through ``amp.decorate`` + ``AdamW`` +
``jit.TrainStep`` like every causal LM here and shares the region
vocabulary (``observability/regions.py``); the delta rule carries a
``jax.named_scope("gated_delta_rule")`` under ``attn_core``, the sparse
block ``moe_router`` and ``moe_experts`` under ``mlp``.

**Serving is not there yet.**  ``serving.Engine`` pages keys and values;
three of four layers here keep a fixed-size state a request instead (the
conv's last positions and the ``(Hv, dk, dv)`` matrix), which is a cache
kind the allocator does not have, and the expert layer has no paged
(ragged-step) path.  ``Engine`` refuses the model and says so
(docs/SERVING.md "Cache kinds").  ``generate()`` recomputes the prefix.

Left out, as in ``benchmark/reference/qwen3_next_ref.py``: the
checkpoint's multi-token-prediction module and the router's auxiliary
loss.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.moe import DroplessMoE
from ..distributed.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                     VocabParallelEmbedding)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..observability.regions import region
from .llama import LlamaForCausalLM, _weight_attr, causal_lm_loss  # noqa: F401

__all__ = ["PRESETS", "Qwen3NextConfig", "Qwen3NextForCausalLM",
           "Qwen3NextModel", "causal_lm_loss", "moe_load_metrics",
           "qwen3_next", "record_moe_load"]


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # the softmax-attention layers
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 262144
    # the gated-delta layers
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # the sparse block
    num_experts: int = 512              # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # what LlamaForCausalLM and serving.Engine read off a causal LM's config
    tie_word_embeddings: bool = False
    loss_seq_chunks: int = 1
    pipeline_stages: int = 1

    def __post_init__(self):
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads must be a multiple of "
                             "linear_num_key_heads")
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)

    def is_full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


PRESETS = {
    "qwen3-next-80b-a3b": Qwen3NextConfig(),
    # every ratio of the published model at widths a CPU test can hold:
    # 2 value heads a key head, 8 query heads a kv head, a quarter of the
    # head rotated, a period of 4
    "tiny": Qwen3NextConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=1, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, max_position_embeddings=512),
}


class Qwen3NextRMSNorm(Layer):
    """RMSNorm whose stored weight is the offset from 1."""

    def __init__(self, dim: int, eps: float):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter(
            (dim,), default_initializer=I.Constant(0.0))

    def forward(self, x):
        with region("norm"):
            return F.rms_norm(x, 1.0 + self.weight.astype(jnp.float32),
                              self.eps)


class _PlainRMSNorm(Layer):
    """The gated-delta mixer's own norm: a plain weight (ones)."""

    def __init__(self, dim: int):
        super().__init__()
        self.weight = self.create_parameter(
            (dim,), default_initializer=I.Constant(1.0))


class _ConvKernel(Layer):
    """A depthwise conv's kernel, ``(channels, width)``."""

    def __init__(self, channels: int, width: int, attr):
        super().__init__()
        self.weight = self.create_parameter((channels, width), attr=attr)


def depthwise_causal_conv(u, w):
    """``u`` ``(B, S, C)``, ``w`` ``(C, K)``: ``c_t = sum_j w[:, j] *
    u_{t-K+1+j}`` with zero history, every channel on its own."""
    kk = w.shape[1]
    s = u.shape[1]
    up = jnp.pad(u, ((0, 0), (kk - 1, 0), (0, 0)))
    w = w.astype(u.dtype)
    return sum(up[:, j:j + s] * w[:, j] for j in range(kk))


def l2_normalise(x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def delta_core(q, k, v, g, beta):
    """q, k ``(B, S, Hk, dk)`` after the conv, v ``(B, S, Hv, dv)``, g and
    beta ``(B, S, Hv)``: L2-normalise q and k, scale q, and run the gated
    delta rule in chunks of 64 positions; key head ``j`` serves value
    heads ``(Hv / Hk) j ..`` inside the rule, unrepeated."""
    from ..incubate.nn.functional import gated_delta_rule
    return gated_delta_rule(l2_normalise(q) * q.shape[-1] ** -0.5,
                            l2_normalise(k), v, g, beta)


class Qwen3NextAttention(Layer):
    """The gated softmax-attention mixer."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nq, nk = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        attr = _weight_attr(cfg)
        self.q_proj = ColumnParallelLinear(h, 2 * nq, has_bias=False,
                                           weight_attr=attr)
        self.k_proj = ColumnParallelLinear(h, nk, has_bias=False,
                                           weight_attr=attr)
        self.v_proj = ColumnParallelLinear(h, nk, has_bias=False,
                                           weight_attr=attr)
        self.o_proj = RowParallelLinear(nq, h, has_bias=False,
                                        weight_attr=attr)
        self.q_norm = Qwen3NextRMSNorm(d, cfg.rms_norm_eps)
        self.k_norm = Qwen3NextRMSNorm(d, cfg.rms_norm_eps)

    def forward(self, x, cos, sin):
        cfg = self.cfg
        b, s = x.shape[:2]
        d, nh, nkv = (cfg.head_dim, cfg.num_attention_heads,
                      cfg.num_key_value_heads)
        rot = cfg.rotary_dim
        with region("attn_proj"):
            qg = self.q_proj(x).reshape(b, s, nh, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
            k = self.k_proj(x).reshape(b, s, nkv, d)
            v = self.v_proj(x).reshape(b, s, nkv, d)
        q, k = self.q_norm(q), self.k_norm(k)
        with region("attn_proj"):
            qr, kr = F.apply_rotary_pos_emb(q[..., :rot], k[..., :rot],
                                            cos, sin)
            q = jnp.concatenate([qr, q[..., rot:]], axis=-1)
            k = jnp.concatenate([kr, k[..., rot:]], axis=-1)
        with region("attn_core"):
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        with region("attn_proj"):
            out = out.reshape(b, s, nh * d) \
                * jax.nn.sigmoid(gate.reshape(b, s, nh * d))
            return self.o_proj(out)


class Qwen3NextGatedDeltaNet(Layer):
    """The gated-delta mixer."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        attr = _weight_attr(cfg)
        self.in_proj_qkvz = ColumnParallelLinear(
            h, 2 * hk * dk + 2 * hv * dv, has_bias=False, weight_attr=attr)
        self.in_proj_ba = ColumnParallelLinear(h, 2 * hv, has_bias=False,
                                               weight_attr=attr)
        self.conv1d = _ConvKernel(2 * hk * dk + hv * dv,
                                  cfg.linear_conv_kernel_dim, attr)
        # the release draws A_log from log(uniform(0, 16)) and sets
        # dt_bias to ones; one-dimensional leaves that are neither a norm
        # nor a bias
        self.A_log = self.create_parameter(
            (hv,), default_initializer=lambda k, s, d: jnp.log(
                jax.random.uniform(k, s, jnp.float32, 1e-3, 16.0)).astype(d))
        self.dt_bias = self.create_parameter(
            (hv,), default_initializer=I.Constant(1.0))
        self.norm = _PlainRMSNorm(dv)
        self.out_proj = RowParallelLinear(hv * dv, h, has_bias=False,
                                          weight_attr=attr)

    def forward(self, x):
        cfg = self.cfg
        b, s = x.shape[:2]
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        r = hv // hk
        with region("attn_proj"):
            qkvz = self.in_proj_qkvz(x).reshape(b, s, hk,
                                                2 * dk + 2 * r * dv)
            ba = self.in_proj_ba(x).reshape(b, s, hk, 2 * r)
            z = qkvz[..., 2 * dk + r * dv:].reshape(b, s, hv, dv)
            u = jnp.concatenate(
                [qkvz[..., :dk].reshape(b, s, hk * dk),
                 qkvz[..., dk:2 * dk].reshape(b, s, hk * dk),
                 qkvz[..., 2 * dk:2 * dk + r * dv].reshape(b, s, hv * dv)],
                axis=-1)
            u = jax.nn.silu(depthwise_causal_conv(u, self.conv1d.weight))
            q = u[..., :hk * dk].reshape(b, s, hk, dk)
            k = u[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
            v = u[..., 2 * hk * dk:].reshape(b, s, hv, dv)
            beta = jax.nn.sigmoid(
                ba[..., :r].reshape(b, s, hv).astype(jnp.float32))
            g = -jnp.exp(self.A_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., r:].reshape(b, s, hv).astype(jnp.float32)
                + self.dt_bias.astype(jnp.float32))
        with region("attn_core"):
            # the rule keeps what its backward needs itself: a state and
            # a (64, 64) inverse a chunk from the kernel, its operands
            # alone (under jax.checkpoint) from the XLA composition
            o = delta_core(q, k, v, g, beta)
        with region("norm"):
            o = F.rms_norm(o, self.norm.weight.astype(jnp.float32),
                           cfg.rms_norm_eps)
        with region("attn_proj"):
            o = (o * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
            return self.out_proj(o.reshape(b, s, hv * dv))


class Qwen3NextDecoderLayer(Layer):
    supports_cache = False  # no dense cache: generate() recomputes
    supports_paged = False  # serving needs a cache kind that is not there

    def __init__(self, cfg: Qwen3NextConfig, index: int):
        super().__init__()
        self.full_attention = cfg.is_full_attention(index)
        self.input_layernorm = Qwen3NextRMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        if self.full_attention:
            self.self_attn = Qwen3NextAttention(cfg)
        else:
            self.linear_attn = Qwen3NextGatedDeltaNet(cfg)
        self.post_attention_layernorm = Qwen3NextRMSNorm(cfg.hidden_size,
                                                         cfg.rms_norm_eps)
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, held=cfg.experts_held,
            shared_width=cfg.shared_expert_intermediate_size,
            norm_topk_prob=cfg.norm_topk_prob, weight_attr=_weight_attr(cfg))

    def forward(self, x, cos, sin):
        a = self.input_layernorm(x)
        mixed = self.self_attn(a, cos, sin) if self.full_attention \
            else self.linear_attn(a)
        with region("attn_proj"):
            x = x + mixed
        h = self.mlp(self.post_attention_layernorm(x))
        with region("mlp"):
            return x + h


class Qwen3NextModel(Layer):
    decoder_layer_cls = Qwen3NextDecoderLayer

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                   cfg.hidden_size)
        from ..nn.layers_common import LayerList
        self.layers = LayerList([Qwen3NextDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = Qwen3NextRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, position_ids=None):
        cfg = self.cfg
        if attn_mask is not None:
            raise NotImplementedError(
                "Qwen3NextModel has the uncached causal forward only")
        with region("embed"):
            x = self.embed_tokens(input_ids)
        with region("attn_proj"):
            cos, sin = F.rope_cos_sin(
                input_ids.shape[1], cfg.rotary_dim, base=cfg.rope_theta,
                dtype=jnp.float32, position_ids=position_ids)
        for layer in self.layers:
            x = layer(x, cos, sin)
        return self.norm(x)


class Qwen3NextForCausalLM(LlamaForCausalLM):
    model_cls = Qwen3NextModel
    # what serving.Engine says when it refuses this model
    paged_serving_needs = (
        "a cache kind that holds a fixed-size state per slot (the gated-"
        "delta layers' conv history and (Hv, dk, dv) matrix) beside the "
        "pages of every fourth layer, and supports_paged on the expert "
        "layer (distributed.moe.DroplessMoE has no ragged-step path)")


def qwen3_next(name_or_config="tiny", **overrides) -> Qwen3NextForCausalLM:
    cfg = (PRESETS[name_or_config] if isinstance(name_or_config, str)
           else name_or_config)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return Qwen3NextForCausalLM(cfg)


def moe_load_metrics(model):
    """``TrainStep(..., extra_metrics=moe_load_metrics(model))``: the
    step's metrics then carry ``moe.rows_held`` (assignments the held
    experts took, summed over the layers), ``moe.expert_rows_max`` and
    ``moe.expert_rows_mean`` (the fullest held expert's rows against the
    mean, the worst layer).  It costs one more forward a step, so a timed
    path does not ask for it; :func:`record_moe_load` puts fetched values
    into the telemetry registry."""
    from ..nn.layer import _swapped_params

    def metrics(state, batch):
        with _swapped_params(model, state["params"]):
            model.model(batch["input_ids"])         # the layers, no head
        loads = {k: jnp.stack([layer.mlp.load[k]
                               for layer in model.model.layers])
                 for k in ("rows_held", "expert_rows_max",
                           "expert_rows_mean")}
        worst = jnp.argmax(loads["expert_rows_max"])
        return {"moe.rows_held": jnp.sum(loads["rows_held"]),
                "moe.expert_rows_max": loads["expert_rows_max"][worst],
                "moe.expert_rows_mean": loads["expert_rows_mean"][worst]}

    return metrics


def record_moe_load(metrics: dict) -> None:
    """Under ``observability.enable()``: ``moe.rows_held`` counts on, the
    two loads go to histograms.  A no-op when telemetry is off."""
    from .. import observability as obs

    reg = obs.get_registry()
    if reg is None:
        return
    reg.counter("moe.rows_held").inc(int(metrics["moe.rows_held"]))
    for name in ("moe.expert_rows_max", "moe.expert_rows_mean"):
        reg.histogram(name).observe(float(metrics[name]))
