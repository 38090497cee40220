"""Mixtral-style sparse-MoE causal LM (BASELINE.json configs[2]: MoE with
EP all-to-all).

Reference capability: the MoE model family the reference core enables via
incubate/distributed/models/moe (the full model lives in PaddleNLP —
SURVEY.md §0 scope note).  Reuses the Llama blocks; the MLP becomes an
expert-parallel MoELayer routed by a GShard/Switch gate.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from ..distributed.moe import GATES, MoELayer
from ..nn.layer import Layer
from ..observability.regions import region
from .llama import (LlamaAttention, LlamaConfig, LlamaForCausalLM, LlamaMLP,
                    LlamaModel, LlamaRMSNorm)


@dataclasses.dataclass
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    gate: str = "gshard"            # "gshard" (top-2) | "switch" (top-1)
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.01


PRESETS = {
    "mixtral-8x7b": MixtralConfig(
        hidden_size=4096, intermediate_size=14336, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=8, num_experts=8),
    "tiny": MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, num_experts=4, capacity_factor=2.0),
}


class MixtralDecoderLayer(Layer):
    returns_aux = True      # train forward returns (x, router_aux_loss)
    supports_cache = True   # cached inference (router aux ignored)

    def __init__(self, cfg: MixtralConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(cfg)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg)
        self.block_sparse_moe = MoELayer(
            cfg.hidden_size, expert=lambda: LlamaMLP(cfg),
            num_experts=cfg.num_experts, gate=cfg.gate, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor)

    def forward(self, x, cos, sin, attn_mask=None, cache=None,
                seq_lens=None):
        if cache is not None:
            # cached inference: attention uses the KV cache; the MoE block
            # is per-token so it works unchanged (router aux is an
            # inference no-op)
            attn, cache = self.self_attn(self.input_layernorm(x), cos, sin,
                                         attn_mask, cache=cache,
                                         seq_lens=seq_lens)
            with region("attn_proj"):
                x = x + attn
            with region("mlp"):
                x = x + self.block_sparse_moe(
                    self.post_attention_layernorm(x))
            return x, cache
        # the shared regions (observability/regions.py): attention and the
        # experts carry theirs by inheritance; the router and the residual
        # adds are the expert block's
        attn = self.self_attn(self.input_layernorm(x), cos, sin, attn_mask)
        with region("attn_proj"):
            x = x + attn
        with region("mlp"):
            x = x + self.block_sparse_moe(self.post_attention_layernorm(x))
        # aux read immediately after the call, same trace level (the
        # MoELayer contract), then threaded outward through our output
        return x, self.block_sparse_moe.aux_loss


class MixtralModel(LlamaModel):
    decoder_layer_cls = MixtralDecoderLayer


class MixtralForCausalLM(LlamaForCausalLM):
    model_cls = MixtralModel

    def forward(self, input_ids, labels=None, attn_mask=None,
                position_ids=None):
        out = super().forward(input_ids, labels=labels, attn_mask=attn_mask,
                              position_ids=position_ids)
        if labels is None:
            return out  # inference ignores the router loss
        return out + self.cfg.router_aux_loss_coef * self.model._moe_aux


def mixtral(name_or_config="tiny", **overrides) -> MixtralForCausalLM:
    cfg = (PRESETS[name_or_config] if isinstance(name_or_config, str)
           else name_or_config)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return MixtralForCausalLM(cfg)


def causal_lm_loss(model, batch):
    return model(batch["input_ids"], labels=batch["labels"])
