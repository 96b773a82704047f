"""Model operations per token of a decoder-only transformer, from a
configuration file's sizes.

A token at context position t (t earlier tokens) costs, per layer, 2 per
weight of the linears plus 4 t d_model for its attention scores and
weighted values (the same count a dense bf16 model of these sizes needs).
A token whose logits are computed (each decoded token) adds 2 d_model
vocab for the head; a prompt token that only fills the cache does not.
"""

from __future__ import annotations

from . import packed_gemm


def linear_ops(sizes: dict) -> float:
    """Operations of the linears of all layers for one token."""
    per_layer = sum(2.0 * k * n for k, n in packed_gemm.layer_shapes(sizes))
    return per_layer * sizes["num_hidden_layers"]


def attention_ops(sizes: dict, context: int) -> float:
    return 4.0 * context * sizes["hidden_size"] * sizes["num_hidden_layers"]


def head_ops(sizes: dict) -> float:
    return 2.0 * sizes["hidden_size"] * sizes["vocab_size"]


def prompt_ops(sizes: dict, start: int, n: int) -> float:
    """n prompt tokens at positions start .. start + n - 1 (no head)."""
    ctx = n * start + n * (n - 1) / 2          # sum of their context lengths
    return n * linear_ops(sizes) + 4.0 * ctx * sizes["hidden_size"] \
        * sizes["num_hidden_layers"]


def decode_ops(sizes: dict, context: int) -> float:
    """One decoded token with ``context`` earlier tokens."""
    return linear_ops(sizes) + attention_ops(sizes, context) + head_ops(sizes)
