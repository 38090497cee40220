"""The program's GPT family (``models/gpt.py``) built from a
configuration file's sizes: learned positions, LayerNorm, GELU, packed
qkv with biases, tied head."""

from __future__ import annotations


def program_config(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=config["num_attention_heads"],
        max_position_embeddings=max_positions,
        layer_norm_eps=config["layer_norm_eps"],
        tie_word_embeddings=config["tie_word_embeddings"],
        initializer_range=config["assumed"]["initializer_range"],
        **over)


def build_model(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.gpt import GPTForCausalLM

    return GPTForCausalLM(program_config(config, layers, max_positions,
                                         **over))


def loss_fn():
    def causal_lm_loss(model, batch):
        return model(batch["input_ids"], labels=batch["labels"])

    return causal_lm_loss
