"""One packed linear layer: y (M, N) f32 = x (M, K) @ dequant(W) (K, N).

Operations: 2 M K N. Bytes: the packed weights at the plan's bits, one f32
scale per output channel (per K/G group when grouped), the activations at
their stated precision (bf16 for a16; for a-bit codes, b bits each plus one
f32 scale per row), and the f32 output. The least time the chip could take
is the larger of operations over the compute peak of the activations'
precision (bf16, or int8 for integer activations of 8 bits or fewer) and
bytes over the memory bandwidth.
"""

from __future__ import annotations


def ops(M: int, K: int, N: int) -> float:
    return 2.0 * M * K * N


def bytes_moved(M: int, K: int, N: int, *, w_bits: int, a_bits: int | None,
                group_size: int | None = None) -> float:
    weights = K * N * w_bits / 8
    scales = 4.0 * N * (1 if group_size is None else K // group_size)
    if a_bits is None:
        acts = 2.0 * M * K
    else:
        acts = M * K * a_bits / 8 + 4.0 * M
    return weights + scales + acts + 4.0 * M * N


def roofline_s(M: int, K: int, N: int, peaks: dict, *, w_bits: int,
               a_bits: int | None, group_size: int | None = None) -> float:
    """The least seconds one call can take on a chip with ``peaks``."""
    peak = peaks["int8_ops"] if a_bits is not None and a_bits <= 8 \
        else peaks["bf16_flops"]
    return max(ops(M, K, N) / peak,
               bytes_moved(M, K, N, w_bits=w_bits, a_bits=a_bits,
                           group_size=group_size) / peaks["hbm_bytes_per_s"])


def layer_shapes(sizes: dict) -> list[tuple[int, int]]:
    """(K, N) of the packed linears of one decoder layer of a configuration
    file's sizes: q, k, v, o, gate, up, down."""
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    hd = D // sizes["num_attention_heads"]
    kv = sizes["num_key_value_heads"] * hd
    return [(D, D), (D, kv), (D, kv), (D, D), (D, F), (D, F), (F, D)]
