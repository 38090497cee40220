"""Operations and bytes that Qwen3-Next's step needs, from shapes alone,
whatever implements it (``benchmark/reference/qwen3_next_ref.py`` has the
equations).  Layer ``i`` is a gated softmax-attention layer when
``(i + 1) % full_attention_interval == 0`` and a gated-delta layer
otherwise; every layer has a sparse block of which ``num_experts`` routed
experts of the router's ``router_width`` are held here.  Recomputed
operations are never counted."""

from __future__ import annotations

from .transformer import causal_attention_flops


def layer_kinds(config: dict, layers: int) -> tuple:
    """(gated-delta layers, full-attention layers) among the first
    ``layers``."""
    full = layers // config["full_attention_interval"]
    return layers - full, full


def delta_mixer_params(config: dict) -> int:
    """in_proj_qkvz, in_proj_ba, out_proj."""
    h = config["hidden_size"]
    kq = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    vz = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return h * (2 * kq + 2 * vz) + h * 2 * config["linear_num_value_heads"] \
        + vz * h


def full_mixer_params(config: dict) -> int:
    """q_proj (query and gate), k_proj, v_proj, o_proj."""
    h, d = config["hidden_size"], config["head_dim"]
    nq = config["num_attention_heads"] * d
    nk = config["num_key_value_heads"] * d
    return h * 2 * nq + 2 * h * nk + nq * h


def held_share(config: dict) -> float:
    """The part of a token's routed choices that falls on the experts
    held here, a uniform router assumed."""
    return config["num_experts"] / config["router_width"]


def sparse_params_per_token(config: dict) -> float:
    """Router, shared expert and its gate, and the routed experts a token
    is multiplied with here: ``num_experts_per_tok`` times the share
    held."""
    h = config["hidden_size"]
    routed = config["num_experts_per_tok"] * held_share(config) \
        * 3 * h * config["moe_intermediate_size"]
    return h * config["router_width"] \
        + 3 * h * config["shared_expert_intermediate_size"] + h + routed


def matmul_params_per_token(config: dict, layers: int) -> float:
    """Parameters a token is multiplied with: both mixers' matrices, every
    layer's sparse block as above, the output head over the slice of the
    vocabulary; not the input embedding."""
    delta, full = layer_kinds(config, layers)
    return delta * delta_mixer_params(config) \
        + full * full_mixer_params(config) \
        + layers * sparse_params_per_token(config) \
        + config["hidden_size"] * config["vocab_size"]


def delta_rule_flops_per_token(config: dict) -> float:
    """The recurrence of one layer, forward: per value head the state
    ``(dk, dv)`` is decayed, read with k, written with k d^T and read with
    q: ``6 dk dv``."""
    return 6.0 * config["linear_key_head_dim"] \
        * config["linear_value_head_dim"] * config["linear_num_value_heads"]


def delta_rule_bytes_per_token(config: dict, itemsize: int = 2) -> float:
    """One layer, forward and backward: q, k (a key head each), v, g and
    beta (float32, a value head each) read and o written; the same read
    again with do, and dq, dk, dv, dg, dbeta written."""
    qk = 2 * config["linear_num_key_heads"] * config["linear_key_head_dim"] \
        * itemsize
    hv = config["linear_num_value_heads"]
    v = hv * config["linear_value_head_dim"] * itemsize
    gb = 2 * hv * 4
    inputs = qk + v + gb
    return float((inputs + v) + (inputs + v) + inputs)


def train_flops_per_token(config: dict, layers: int, seq: int) -> float:
    """6 x multiplied parameters + the full layers' causal attention + the
    delta rule's recurrence (x 3 with the backward), for one token of a
    sequence of ``seq``."""
    delta, full = layer_kinds(config, layers)
    att = causal_attention_flops(config, full, 1, seq, True) / seq
    return 6.0 * matmul_params_per_token(config, layers) + att \
        + 3.0 * delta * delta_rule_flops_per_token(config)


def expert_rows(config: dict, tokens: int) -> float:
    """Rows a layer's held experts are expected to take a step."""
    return tokens * config["num_experts_per_tok"] * held_share(config)


def experts_flops(config: dict, layers: int, tokens: int) -> float:
    """The grouped products at the expected rows: three matrices, forward,
    data gradient and weight gradient."""
    return 3.0 * expert_rows(config, tokens) * 3 * 2.0 \
        * config["hidden_size"] * config["moe_intermediate_size"] * layers


def experts_bytes(config: dict, layers: int, itemsize: int = 2) -> float:
    """The held experts' leaves read and their gradients written."""
    return 2.0 * config["num_experts"] * 3 * config["hidden_size"] \
        * config["moe_intermediate_size"] * itemsize * layers
