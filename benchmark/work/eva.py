"""Operations and bytes that EVA, chunked linearized attention, needs,
from shapes alone, whatever implements it (``benchmark/reference/
evabyte_ref.py`` has the equations).  A query at position ``t`` of window
``w = t // W`` sees the ``t % W + 1`` keys of its own window and one
summary per chunk of ``c`` keys of the earlier windows, ``(W / c) * w`` of
them; every completed chunk is summarised once."""

from __future__ import annotations

# q, k, v, o (no grouping: 32 KV heads) and the SwiGLU's three
from .transformer import layer_matmul_params  # noqa: F401


def keys_seen(position: int, window: int, chunk: int) -> int:
    """Keys and summaries under the one softmax of a query at
    ``position``."""
    w = position // window
    return position - w * window + 1 + (window // chunk) * w


def rows_held(positions: int, window: int, chunk: int) -> int:
    """Rows of k (and of v) a request of ``positions`` written positions
    holds: the open window's, and one per completed chunk."""
    return positions % window + positions // chunk


def row_bytes(config: dict, layers: int, itemsize: int = 2) -> int:
    """k and v of one row of a page over all layers: a position of the
    open window or a chunk's summary, the same bytes."""
    return 2 * config["num_attention_heads"] * config["head_dim"] \
        * itemsize * layers


def attention_flops_per_token(config: dict, layers: int, seen: float) -> float:
    """QK^T and PV of one query over ``seen`` keys and summaries."""
    h = config["num_attention_heads"] * config["head_dim"]
    return 4.0 * h * seen * layers


def summariser_flops_per_token(config: dict, layers: int) -> float:
    """Per position of a completed chunk: phi . k, and its share of the
    two weighted sums (of keys, of values)."""
    h = config["num_attention_heads"] * config["head_dim"]
    return 6.0 * h * layers


def head_params(config: dict) -> int:
    """The head as the step multiplies it: all prediction heads' columns
    exist, plain decoding reads head 0's; the algorithm needs those."""
    return config["hidden_size"] * config["vocab_size"]


def serve_flops(config: dict, layers: int, processed: float, emitted: float,
                seen: float) -> float:
    """Forward operations the algorithm needs: every processed byte
    through the layers' matrices, its attention over the keys and
    summaries it sees, its share of its chunk's summary; every emitted
    byte through head 0."""
    per = 2.0 * layer_matmul_params(config, layers) \
        + attention_flops_per_token(config, layers, seen) \
        + summariser_flops_per_token(config, layers)
    return per * processed + 2.0 * head_params(config) * emitted
