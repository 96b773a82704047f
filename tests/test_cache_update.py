"""The decode cache write (``layers._cache_update``) against the row writes
it replaces: a ``vmap`` of ``dynamic_update_slice`` per cache array.

A layer whose cache row holds at most ``KV_SELECT_MAX_ROW_BYTES`` (K, V and
their scales together) writes a single new position with a select over
positions; a longer row, or an update of several positions, keeps the row
writes. Either way every byte of the cache must come out the same,
clamped start positions included, and ``kv_cache_write_total`` names the
route taken.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.qplan import get_plan
from repro.launch import steps as St
from repro.models import layers as L
from repro.models import lm
from repro.obs import metrics as obs_metrics


def _row_writes(cache, new, pos):
    def upd(c, x, s):
        return jax.lax.dynamic_update_slice(c, x, (s,) + (0,) * (c.ndim - 1))
    return {n: jax.vmap(upd)(cache[n], new[n], pos) for n in new}


def _arrays(key, B, S, KV, hd, dtype):
    """A layer's cache arrays: int8 K/V with f32 scales, or bf16 K/V."""
    kk, kv, ks, kt = jax.random.split(key, 4)
    if dtype == "int8":
        return {"k": jax.random.randint(kk, (B, S, KV, hd), -127, 128,
                                        jnp.int8),
                "v": jax.random.randint(kv, (B, S, KV, hd), -127, 128,
                                        jnp.int8),
                "k_sc": jax.random.uniform(ks, (B, S, KV), jnp.float32),
                "v_sc": jax.random.uniform(kt, (B, S, KV), jnp.float32)}
    return {"k": jax.random.normal(kk, (B, S, KV, hd), jnp.bfloat16),
            "v": jax.random.normal(kv, (B, S, KV, hd), jnp.bfloat16)}


def _row_bytes(cache):
    return sum(x[0].size * x.dtype.itemsize for x in cache.values())


# (B, S, KV, hd, new positions T, pos of each row)
MIXED = (0, 7, 15, 3, 40)
CASES = {
    "pos0": (4, 16, 2, 8, 1, (0, 0, 0, 0)),
    "mid": (4, 16, 2, 8, 1, (8, 8, 8, 8)),
    "last": (4, 16, 2, 8, 1, (15, 15, 15, 15)),
    "past_end": (4, 16, 2, 8, 1, (16, 99, 2**30, -3)),   # -3 is S - 3
    "mixed": (5, 16, 2, 8, 1, MIXED),
    "chunk": (5, 16, 2, 8, 3, MIXED),      # T > 1: clamped to S - T
}


@pytest.mark.parametrize("limit", ["at_row", "below_row", "module"])
@pytest.mark.parametrize("dtype", ["int8", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_cache_update_matches_row_writes(monkeypatch, case, dtype, limit):
    """Bit for bit the row writes, on both sides of the byte limit: a limit
    equal to the layer's row bytes takes the select, one byte below takes
    the rows, and the module's own limit takes the select at these sizes."""
    B, S, KV, hd, T, pos = CASES[case]
    cache = _arrays(jax.random.PRNGKey(0), B, S, KV, hd, dtype)
    new = {n: x[:, :T] for n, x in
           _arrays(jax.random.PRNGKey(1), B, S, KV, hd, dtype).items()}
    pos = jnp.asarray(pos, jnp.int32)
    row = _row_bytes(cache)
    if limit != "module":
        monkeypatch.setattr(L, "KV_SELECT_MAX_ROW_BYTES",
                            row if limit == "at_row" else row - 1)
    with obs_metrics.scoped() as reg:
        got = L._cache_update(cache, new, pos)
    want = _row_writes(cache, new, pos)
    assert set(got) == set(cache)
    for n in cache:
        assert got[n].dtype == cache[n].dtype and got[n].shape == cache[n].shape
        np.testing.assert_array_equal(np.asarray(got[n]), np.asarray(want[n]))
    route = "select" if T == 1 and limit != "below_row" else "rows"
    assert reg.get("kv_cache_write_total", route=route) == 1
    assert reg.counter_total("kv_cache_write_total") == 1


@pytest.mark.parametrize("S,route", [(192, "select"), (6160, "rows")])
def test_cache_route_at_the_benchmark_widths(S, route):
    """qwen1.5-0.5b's int8 cache (16 KV heads of 64) holds 2,176 bytes a
    position: 192 positions a row take the select, 6,160 keep the rows."""
    B, KV, hd = 2, 16, 64
    cache = _arrays(jax.random.PRNGKey(2), B, S, KV, hd, "int8")
    assert _row_bytes(cache) == S * 2176
    new = {n: x[:, :1] for n, x in
           _arrays(jax.random.PRNGKey(3), B, S, KV, hd, "int8").items()}
    pos = jnp.asarray([S // 3, S - 1], jnp.int32)
    with obs_metrics.scoped() as reg:
        got = jax.jit(L._cache_update)(cache, new, pos)
    assert reg.label_values("kv_cache_write_total", "route") == [route]
    want = _row_writes(cache, new, pos)
    for n in cache:
        np.testing.assert_array_equal(np.asarray(got[n]), np.asarray(want[n]))


def test_decode_step_same_on_both_routes(monkeypatch):
    """A decode step of a small int8-cache qwen1.5 gives the same logits and
    caches whichever route writes the cache, with rows at different
    positions."""
    B, P, G = 3, 12, 4
    cfg = dataclasses.replace(reduce_for_smoke(get_config("qwen1.5-0.5b")),
                              n_layers=2, kv_cache_dtype="int8",
                              quant=get_plan("w2a16"))
    params = lm.quantize_tree(lm.init_params(jax.random.PRNGKey(0), cfg), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                cfg.vocab_size)
    _, caches = jax.jit(St.make_prefill_step(cfg, max_len=P + G))(
        params, {"tokens": tokens})
    batch = {"tokens": tokens[:, -1:],
             "pos": jnp.asarray([P, P - 5, P + G + 1], jnp.int32)}

    def decode(limit):
        monkeypatch.setattr(L, "KV_SELECT_MAX_ROW_BYTES", limit)
        with obs_metrics.scoped() as reg:
            out = jax.jit(St.make_decode_step(cfg))(params, caches, batch)
        return out, reg.label_values("kv_cache_write_total", "route")

    (sel_logits, sel_caches), sel_route = decode(L.KV_SELECT_MAX_ROW_BYTES)
    (row_logits, row_caches), row_route = decode(0)
    assert (sel_route, row_route) == (["select"], ["rows"])
    np.testing.assert_array_equal(np.asarray(sel_logits),
                                  np.asarray(row_logits))
    for a, b in zip(jax.tree.leaves(sel_caches), jax.tree.leaves(row_caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
