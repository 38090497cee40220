"""The program's Llama-family model (``models/llama.py``) built from a
configuration file's sizes.  Mistral-7B runs through it: GQA, RoPE,
RMSNorm, SwiGLU, untied head."""

from __future__ import annotations


def program_config(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        initializer_range=config["assumed"]["initializer_range"],
        **over)


def build_model(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.llama import LlamaForCausalLM

    return LlamaForCausalLM(program_config(config, layers, max_positions,
                                           **over))


def loss_fn():
    from paddle_tpu.models.llama import causal_lm_loss

    return causal_lm_loss
