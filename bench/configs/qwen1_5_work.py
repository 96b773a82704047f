"""Work counts of a Qwen1.5 configuration file: the packed linears of one
model step and the model's operations per token.

Every layer runs seven packed linears, q, k, v, o, gate, up and down; under
grouped-query attention k and v have ``num_key_value_heads`` heads of
``hidden_size / num_attention_heads``. The head, tied or not, is one bf16
(hidden, vocab) product and no packed linear.

A token at context position t (t earlier tokens) costs, per layer, 2 per
weight of the linears plus 4 t d_model for its attention scores and
weighted values over its query heads (the same count a dense bf16 model of
these sizes needs). A token whose logits are computed (each decoded token)
adds 2 d_model vocab for the head; a prompt token that only fills the cache
does not.
"""

from __future__ import annotations


def _layer_shapes(config: dict) -> list[tuple[int, int]]:
    """(K, N) of one layer's packed linears: q, k, v, o, gate, up, down."""
    D, F = config["hidden_size"], config["intermediate_size"]
    kv = config["num_key_value_heads"] * (D // config["num_attention_heads"])
    return [(D, D), (D, kv), (D, kv), (D, D), (D, F), (D, F), (F, D)]


def dense_calls(config: dict) -> list[tuple[int, int, int]]:
    """(K, N, calls) of the packed linears one model step runs, each call
    over all of the step's rows."""
    L = config["num_hidden_layers"]
    return [(k, n, L) for k, n in _layer_shapes(config)]


def linear_ops(config: dict) -> float:
    """Operations of the linears of all layers for one token."""
    per_layer = sum(2.0 * k * n for k, n in _layer_shapes(config))
    return per_layer * config["num_hidden_layers"]


def attention_ops(config: dict, context: int) -> float:
    return 4.0 * context * config["hidden_size"] \
        * config["num_hidden_layers"]


def head_ops(config: dict) -> float:
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def prompt_ops(config: dict, start: int, n: int) -> float:
    """n prompt tokens at positions start .. start + n - 1 (no head)."""
    ctx = n * start + n * (n - 1) / 2          # sum of their context lengths
    return n * linear_ops(config) + 4.0 * ctx * config["hidden_size"] \
        * config["num_hidden_layers"]


def decode_ops(config: dict, context: int) -> float:
    """One decoded token with ``context`` earlier tokens."""
    return linear_ops(config) + attention_ops(config, context) \
        + head_ops(config)
