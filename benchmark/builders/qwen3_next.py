"""The program's Qwen3-Next (``models/qwen3_next.py``) built from a
configuration file's sizes: three gated-delta layers to every gated
softmax-attention layer, a sparse block of routed experts with a gated
shared expert.  The file's ``num_experts`` counts the experts held here
(``first_expert`` the first of them) and ``router_width`` the experts the
router scores, the published ``num_experts``."""

from __future__ import annotations


def program_config(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig

    return Qwen3NextConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=layers,
        full_attention_interval=config["full_attention_interval"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(config["rope_theta"]),
        max_position_embeddings=max_positions,
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        num_experts=config["router_width"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        experts_held=(config["first_expert"], config["num_experts"]),
        rms_norm_eps=config["rms_norm_eps"],
        tie_word_embeddings=config["tie_word_embeddings"],
        initializer_range=config["assumed"]["initializer_range"],
        **over)


def build_model(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.qwen3_next import Qwen3NextForCausalLM

    return Qwen3NextForCausalLM(program_config(config, layers, max_positions,
                                               **over))


def loss_fn():
    from paddle_tpu.models.qwen3_next import causal_lm_loss

    return causal_lm_loss
