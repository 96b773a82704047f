"""The comparison that decides ``correct``, driven through a whole run at a
size the CPU holds: the harness's look for a chip is skipped and the
kernels run on the program's ``ref`` backend (so the dispatch checks fail
here by design; these tests read the logit gap).

- a sound run keeps its widest served-token gap under the limit;
- the control (the reference with the activations the configuration keeps
  in bfloat16 rounded to float8) reads over it, on three seeds;
- a run whose timed path alters the token it produces, or whose decode
  step hands back the cache unchanged, reads over it and comes out not
  correct.

The same holds for a configuration of another layout that brings only its
own file (``data/qwen1.5-gqa-untied-tiny.json``: grouped-query attention,
8 query heads over 2 key/value heads, an untied head), run through the
harness's generic path.

The limit here, 0.05, is set from these tiny-size readings (program about
0.01, control about 0.15 over seeds 1-3, CPU); the chip cells' limits come
from bench/calibrate.py at the cells' own sizes.
"""

import dataclasses
import hashlib
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import calibrate, configfile, harness, weights as W

ROOT = Path(__file__).resolve().parents[2]
GQA_FILE = ROOT / "bench/tests/data/qwen1.5-gqa-untied-tiny.json"
TINY_LIMIT = 0.05
D, L, V, H = 256, 4, 4096, 4
# sha256 of the weight trees that tiny() gives on seeds 1 and 2, as the
# weight builder made them before it moved into the configuration's module
WEIGHT_DIGESTS = {
    1: "cba87bd6e014d60829799a0d609ed00cfafea64ca5be0dcfab5505cfaac6f76a",
    2: "af94e96f544f42a05f2d77d9939da9f17c7827d1a3650c34cdba93b733e969ae"}

BATCH = {"loop": "batch", "slots": 4,
         "prompt": {"dist": "uniform", "min": 48, "max": 48},
         "output": {"dist": "uniform", "min": 64, "max": 64}}
METRICS = {"end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                          {"name": "setup_s", "unit": "s"}],
           "per_layer": []}


def tiny():
    from repro.configs import get_config

    config = json.loads(
        (ROOT / "bench/configs/qwen1.5-0.5b-w2a16.json").read_text())
    config.update(hidden_size=D, intermediate_size=D * 11 // 4,
                  num_hidden_layers=L, num_attention_heads=H,
                  num_key_value_heads=H, vocab_size=V,
                  logit_gap_limits={"tiny": TINY_LIMIT})
    pcfg = dataclasses.replace(get_config(config["arch"]), n_layers=L,
                               d_model=D, n_heads=H, n_kv_heads=H,
                               d_ff=D * 11 // 4, vocab_size=V)
    return config, pcfg


def gqa():
    """The test-only GQA, untied configuration and its program config."""
    from repro.configs import get_config

    config = json.loads(GQA_FILE.read_text())
    pcfg = dataclasses.replace(
        get_config(config["arch"]), n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        tie_embeddings=config["tie_word_embeddings"])
    return config, pcfg


CELL = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", [1, 2])
def test_weights_match_the_frozen_digests(seed):
    config, _ = tiny()
    assert _digest(W.make_weights(config, seed)) == WEIGHT_DIGESTS[seed]


def test_gqa_reference_equals_mha_with_each_kv_head_repeated():
    import jax
    import jax.numpy as jnp

    config, _ = gqa()
    Hq, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // Hq
    w = W.make_weights(config, 5)
    assert w["blocks"]["l0"]["attn"]["wk"]["w"].shape[-1] == KV * hd
    assert "in_embed" in w and "tok_embed" not in w

    def repeat(x):          # (..., KV * hd) -> (..., Hq * hd)
        s = x.shape[:-1]
        x = jnp.repeat(x.reshape(*s, KV, hd), Hq // KV, axis=-2)
        return x.reshape(*s, Hq * hd)

    mha = jax.tree.map(lambda x: x, w)
    attn = mha["blocks"]["l0"]["attn"]
    for name in ("wk", "wv"):
        attn[name] = {k: repeat(v) for k, v in attn[name].items()}
    ref = configfile.module(config, "reference")
    tokens = np.random.default_rng(5).integers(0, config["vocab_size"], 40)
    rows = np.arange(40)
    got = ref.logits(w, config, tokens, rows, length=ref.Q_BLOCK, n_rows=40)
    want = ref.logits(mha, dict(config, num_key_value_heads=Hq), tokens,
                      rows, length=ref.Q_BLOCK, n_rows=40)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dispatch_checks_hold_each_packed_op():
    from bench import check

    counts = check.dispatch_counts({
        "kernel_dispatch_total{backend=pallas,op=dequant_matmul}": 168,
        "kernel_dispatch_total{backend=ref,op=expert_dequant_matmul}": 3})
    one = check.dispatch_checks(
        counts, configfile.packed_ops({"packed_op": "dequant_matmul"}))
    assert one["pallas_dispatches"]["value"] == 168
    two = check.dispatch_checks(counts, configfile.packed_ops(
        {"packed_op": ["dequant_matmul", "expert_dequant_matmul"]}))
    assert two["pallas_dispatches"]["value"] == 0
    assert one["non_pallas_dispatches"]["value"] == 3


def test_program_keys_read_nested_attributes():
    config, pcfg = gqa()
    assert harness.program_value(pcfg, "n_kv_heads") == 2
    assert harness.program_value(pcfg, "quant.w_bits") == pcfg.quant.w_bits
    with pytest.raises(ValueError, match="differs"):
        harness._program(dict(config, num_key_value_heads=8), BATCH, pcfg)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(seed):
    config, pcfg = tiny()
    r = calibrate.readings(CELL, config, BATCH, seed, 0.5, {},
                           program_cfg=pcfg, backend="ref")
    assert r["sampled_tokens"] >= 256
    assert r["logit_gap"] <= TINY_LIMIT < r["control_gap"]


def _run(mix, fault=None, make=tiny):
    config, pcfg = make()
    return harness.run(CELL, config, mix, METRICS, seed=7, seconds=0.5,
                       trace=False, peaks={}, t_start=time.perf_counter(),
                       program_cfg=pcfg, backend="ref", fault=fault)


def _next_token(logits):
    """Logits whose argmax is the token after the real argmax."""
    import jax.numpy as jnp

    alt = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
    return jnp.where(jnp.arange(logits.shape[-1]) == alt[..., None],
                     0.0, -1e9).astype(logits.dtype)


def test_sound_batch_run_passes_the_gap():
    res = _run(BATCH)
    gap = res["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert res["checks"]["compiles_in_window"]["value"] == 0


def _token_altered(decode):
    def broken(params, caches, batch):
        logits, caches = decode(params, caches, batch)
        return _next_token(logits), caches
    return broken


def _cache_kept(decode):
    import jax
    import jax.numpy as jnp

    def broken(params, caches, batch):
        kept = jax.tree.map(jnp.copy, caches)
        logits, _ = decode(params, caches, batch)
        return logits, kept
    return broken


def test_token_altered_in_the_batch_loop_fails():
    res = _run(BATCH, _token_altered)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_decode_step_that_keeps_its_cache_fails():
    res = _run(BATCH, _cache_kept)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_gqa_untied_config_sound_run_passes_the_gap():
    res = _run(BATCH, make=gqa)
    gap = res["checks"]["logit_gap"]
    assert res["checks"]["sampled_tokens"]["value"] >= 256
    assert gap["value"] <= gap["limit"] == TINY_LIMIT
    assert res["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", [_token_altered, _cache_kept],
                         ids=["token_altered", "cache_kept"])
def test_gqa_untied_config_fault_fails(fault):
    res = _run(BATCH, fault, make=gqa)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_gqa_untied_control_fails_where_the_program_passes():
    config, pcfg = gqa()
    r = calibrate.readings(CELL, config, BATCH, 1, 0.5, {},
                           program_cfg=pcfg, backend="ref")
    assert r["sampled_tokens"] >= 256
    assert r["logit_gap"] <= TINY_LIMIT < r["control_gap"]
