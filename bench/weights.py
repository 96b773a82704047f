"""Seeded random weights for a benchmark configuration.

Made on the device in one jitted call from the seed, in bfloat16 (the dtype
the program serves them in), in the parameter layout ``repro.models.lm``
stacks a uniform decoder in: one ``blocks.l0`` subtree whose leaves carry
the layer index as their leading axis. The reference reads the same tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def device_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps 32
    and silently maps larger seeds to zero)."""
    if seed < 0 or seed >= 2 ** 63:
        raise ValueError(f"seed must be in [0, 2**63): {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _build(key, *, L, D, H, F, V):
    ks = iter(jax.random.split(key, 16))
    f32, bf16 = jnp.float32, jnp.bfloat16

    def dense(din, dout):
        w = jax.random.normal(next(ks), (L, din, dout), f32) * din ** -0.5
        return w.astype(bf16)

    def vec(shape, mean, std):
        return (mean + std * jax.random.normal(next(ks), shape, f32)).astype(bf16)

    return {
        "tok_embed": (0.02 * jax.random.normal(next(ks), (V, D), f32)).astype(bf16),
        "final_norm": {"scale": vec((D,), 1.0, 0.1)},
        "blocks": {"l0": {
            "ln1": {"scale": vec((L, D), 1.0, 0.1)},
            "attn": {
                "wq": {"w": dense(D, D), "b": vec((L, D), 0.0, 0.1)},
                "wk": {"w": dense(D, D), "b": vec((L, D), 0.0, 0.1)},
                "wv": {"w": dense(D, D), "b": vec((L, D), 0.0, 0.1)},
                "wo": {"w": dense(D, D)},
            },
            "ln2": {"scale": vec((L, D), 1.0, 0.1)},
            "mlp": {"w_gate": {"w": dense(D, F)}, "w_up": {"w": dense(D, F)},
                    "w_down": {"w": dense(F, D)}},
        }},
    }


def make_weights(sizes: dict, seed: int) -> dict:
    """The weight tree of a configuration file's sizes (Hugging Face key
    names), made on the default device from ``seed``."""
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError("make_weights lays out multi-head attention only "
                         "(num_key_value_heads == num_attention_heads)")
    if not sizes["tie_word_embeddings"]:
        raise ValueError("make_weights lays out tied embeddings only")
    fn = jax.jit(lambda k: _build(
        k, L=sizes["num_hidden_layers"], D=sizes["hidden_size"],
        H=sizes["num_attention_heads"], F=sizes["intermediate_size"],
        V=sizes["vocab_size"]))
    return fn(device_key(seed))
