"""Seeded random weights of a Qwen1.5 configuration file.

Made on the device in one jitted call from the seed, in bfloat16 (the dtype
the program serves them in), in the parameter layout ``repro.models.lm``
stacks a uniform decoder in: one ``blocks.l0`` subtree whose leaves carry
the layer index as their leading axis. The reference reads the same tree.

Grouped-query attention (``num_key_value_heads`` below
``num_attention_heads``) gives the k and v linears ``num_key_value_heads``
heads of ``hidden_size / num_attention_heads``. Untied embeddings
(``tie_word_embeddings`` false) name the table ``in_embed`` and add the head
``lm_head.w`` (hidden, vocab), drawn last, so the other leaves draw the
same keys tied or untied. The head is drawn as the embedding is, N(0,
0.02), so its logits have the scale of a tied head's, and a ``logit_gap``
limit means the same either way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import device_key


def _build(key, *, L, D, H, KV, F, V, tied):
    ks = iter(jax.random.split(key, 16))
    f32, bf16 = jnp.float32, jnp.bfloat16
    kv = KV * (D // H)

    def normal(shape, std):
        return (std * jax.random.normal(next(ks), shape, f32)).astype(bf16)

    def dense(din, dout):
        w = jax.random.normal(next(ks), (L, din, dout), f32) * din ** -0.5
        return w.astype(bf16)

    def vec(shape, mean, std):
        return (mean + std * jax.random.normal(next(ks), shape, f32)).astype(bf16)

    tree = {
        "tok_embed" if tied else "in_embed": normal((V, D), 0.02),
        "final_norm": {"scale": vec((D,), 1.0, 0.1)},
        "blocks": {"l0": {
            "ln1": {"scale": vec((L, D), 1.0, 0.1)},
            "attn": {
                "wq": {"w": dense(D, D), "b": vec((L, D), 0.0, 0.1)},
                "wk": {"w": dense(D, kv), "b": vec((L, kv), 0.0, 0.1)},
                "wv": {"w": dense(D, kv), "b": vec((L, kv), 0.0, 0.1)},
                "wo": {"w": dense(D, D)},
            },
            "ln2": {"scale": vec((L, D), 1.0, 0.1)},
            "mlp": {"w_gate": {"w": dense(D, F)}, "w_up": {"w": dense(D, F)},
                    "w_down": {"w": dense(F, D)}},
        }},
    }
    if not tied:
        tree["lm_head"] = {"w": normal((D, V), 0.02)}
    return tree


def make_weights(config: dict, seed: int) -> dict:
    """The weight tree of a configuration file's sizes (Hugging Face key
    names), made on the default device from ``seed``."""
    fn = jax.jit(lambda k: _build(
        k, L=config["num_hidden_layers"], D=config["hidden_size"],
        H=config["num_attention_heads"], KV=config["num_key_value_heads"],
        F=config["intermediate_size"], V=config["vocab_size"],
        tied=config["tie_word_embeddings"]))
    return fn(device_key(seed))
