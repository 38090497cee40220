"""The program's EvaByte (``models/evabyte.py``) built from a
configuration file's sizes: EVA chunked linearized attention (exact keys
of the open window beside one summary per chunk of the earlier ones),
RMSNorm with a unit offset, a float32 residual stream, SwiGLU, a head of
``num_pred_heads`` x ``vocab_size`` columns."""

from __future__ import annotations


def program_config(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.evabyte import EvaByteConfig

    return EvaByteConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=config["num_attention_heads"],
        num_pred_heads=config["num_pred_heads"],
        max_position_embeddings=max_positions,
        window_size=config["window_size"],
        chunk_size=config["chunk_size"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        initializer_range=config["assumed"]["initializer_range"],
        **over)


def build_model(config: dict, layers: int, max_positions: int, **over):
    from paddle_tpu.models.evabyte import EvaByteForCausalLM

    return EvaByteForCausalLM(program_config(config, layers, max_positions,
                                             **over))
