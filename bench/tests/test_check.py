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

The limit here, 0.05, is set from these tiny-size readings (program about
0.01, control about 0.15 over seeds 1-3, CPU); the chip cells' limits come
from bench/calibrate.py at the cells' own sizes.
"""

import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import calibrate, harness

ROOT = Path(__file__).resolve().parents[2]
TINY_LIMIT = 0.05
D, L, V, H = 256, 4, 4096, 4

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


CELL = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(seed):
    config, pcfg = tiny()
    r = calibrate.readings(CELL, config, BATCH, seed, 0.5, {},
                           program_cfg=pcfg, backend="ref")
    assert r["sampled_tokens"] >= 256
    assert r["logit_gap"] <= TINY_LIMIT < r["control_gap"]


def _run(mix, fault=None):
    config, pcfg = tiny()
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


def test_token_altered_in_the_batch_loop_fails():
    def fault(decode):
        def broken(params, caches, batch):
            logits, caches = decode(params, caches, batch)
            return _next_token(logits), caches
        return broken

    res = _run(BATCH, fault)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_decode_step_that_keeps_its_cache_fails():
    import jax
    import jax.numpy as jnp

    def fault(decode):
        def broken(params, caches, batch):
            kept = jax.tree.map(jnp.copy, caches)
            logits, _ = decode(params, caches, batch)
            return logits, kept
        return broken

    res = _run(BATCH, fault)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT
