"""Seeded random weights for a benchmark configuration.

The configuration's ``weights`` module (``bench/configfile.py``) lays out
the tree; this module gives it the seed's key. The reference reads the same
tree as the program.
"""

from __future__ import annotations

import jax

from bench import configfile


def device_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps 32
    and silently maps larger seeds to zero)."""
    if seed < 0 or seed >= 2 ** 63:
        raise ValueError(f"seed must be in [0, 2**63): {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_weights(config: dict, seed: int) -> dict:
    """The weight tree of a configuration, made on the default device from
    ``seed`` by the module the configuration's ``weights`` key names."""
    return configfile.module(config, "weights").make_weights(config, seed)
