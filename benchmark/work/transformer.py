"""Operations and bytes that the algorithm needs, from shapes alone,
whatever implements it.  Recomputed operations are never counted.  The
counts are the benchmark's own: the program's
``observability.mfu.causal_lm_flops_per_token`` counts the input
embedding, a lookup, among the multiplied parameters (19 % of a depth-2
Mistral), so it is not used."""

from __future__ import annotations


def matmul_params(config: dict, layers: int) -> int:
    """Parameters a token is multiplied with: every matrix of the layers
    and the output head (tied or not); not the input embedding."""
    h = config["hidden_size"]
    f = config["intermediate_size"]
    d = config["head_dim"]
    nq = config["num_attention_heads"] * d
    nk = config.get("num_key_value_heads",
                    config["num_attention_heads"]) * d
    attn = h * nq + 2 * h * nk + nq * h
    mlp = (3 if config["hidden_act"] == "silu" else 2) * h * f
    return layers * (attn + mlp) + h * config["vocab_size"]


def all_params(config: dict, layers: int, positions: int = 0) -> int:
    """Every parameter the optimizer updates (norms and biases left out:
    under a thousandth of the total)."""
    n = matmul_params(config, layers)
    if not config["tie_word_embeddings"]:
        n += config["hidden_size"] * config["vocab_size"]
    return n + positions * config["hidden_size"]


def causal_attention_flops(config: dict, layers: int, batch: int, seq: int,
                           backward: bool) -> float:
    """QK^T and PV over the causal half of ``seq x seq``: 2 products
    forward, 4 more backward (dV, dP, dQ, dK); the backward pass's
    recomputation of the scores is not counted."""
    h = config["num_attention_heads"] * config["head_dim"]
    products = 6 if backward else 2
    return products * 2.0 * batch * seq * seq * h / 2.0 * layers


def causal_attention_bytes(config: dict, layers: int, batch: int, seq: int,
                           backward: bool, itemsize: int = 2) -> float:
    """q, k, v read and o written forward; q, k, v, o, do read and dq,
    dk, dv written backward."""
    d = config["head_dim"]
    q = batch * seq * config["num_attention_heads"] * d * itemsize
    kv = batch * seq * config.get("num_key_value_heads",
                                  config["num_attention_heads"]) * d * itemsize
    fwd = 2 * q + 2 * kv
    bwd = (3 * q + 2 * kv) + (q + 2 * kv)
    return float(layers * (fwd + (bwd if backward else 0)))


def train_flops_per_token(config: dict, layers: int, seq: int) -> float:
    """6 x multiplied parameters + causal attention, forward and
    backward, for one token of a sequence of ``seq``."""
    att = causal_attention_flops(config, layers, 1, seq, True) / seq
    return 6.0 * matmul_params(config, layers) + att


def adamw_bytes(n_params: int) -> float:
    """Read the float32 master, the gradient as it arrives (bfloat16) and
    two moments; write master, two moments and the bfloat16 copy."""
    return float(n_params) * (4 + 2 + 4 + 4 + 4 + 4 + 4 + 2)


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound holds)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bytes")


# -- serving ----------------------------------------------------------------

def layer_matmul_params(config: dict, layers: int) -> int:
    """The layers' matrices alone: what every processed token, prompt or
    output, is multiplied with."""
    return matmul_params(config, layers) \
        - config["hidden_size"] * config["vocab_size"]


def kv_bytes_per_token(config: dict, layers: int, itemsize: int = 2) -> int:
    """Keys and values of one position over all layers."""
    kv = config.get("num_key_value_heads", config["num_attention_heads"])
    return 2 * kv * config["head_dim"] * itemsize * layers


def live_context(counters: dict, engine: dict):
    """(live tokens in the KV pool, slots in use) over the traced seconds:
    the mean of the polled ``kv_blocks_used`` times the page, and the
    requests in flight at the trace's middle, at most ``max_batch``."""
    polls = counters.get("kv_blocks_polls") or [0]
    live = sum(polls) / len(polls) * engine["page_size"]
    slots = min(engine["max_batch"],
                max(1, counters.get("traced_in_flight", 1)))
    return live, slots


def serve_flops(config: dict, layers: int, processed: float, emitted: float,
                mean_context: float) -> float:
    """Forward operations the algorithm needs: every processed token
    through the layers' matrices, every emitted token through the output
    head, and each processed token's attention over its context (QK^T and
    PV)."""
    h = config["num_attention_heads"] * config["head_dim"]
    head = config["hidden_size"] * config["vocab_size"]
    att = 4.0 * h * mean_context * layers
    return (2.0 * layer_matmul_params(config, layers) + att) * processed \
        + 2.0 * head * emitted
