"""Record the small chip trace that ``test_scope_time.py`` reads
(``data/v5e_scoped.xplane.pb``): three decode steps of a two-layer,
256-wide qwen1.5-shaped model packed at w2a16 with the int8 KV cache, built
and stepped as the benchmark's fixed-batch loop does, traced by the
benchmark's profiler settings inside its window span. The prefill before
them is left out of the trace: each executable in a trace brings the HLO
text of its every operation, and the file is kept small.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Runs on the chip only: the trace's device plane is the TPU's.
"""

import dataclasses
import glob
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
D, L, H, V = 256, 2, 4, 4096        # hidden, layers, heads, vocab
B, P, G = 8, 32, 4                  # rows, prompt tokens, tokens out


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    from bench import harness, weights as W
    from repro.configs import get_config
    from repro.launch import steps as St
    from repro.models import lm

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace.py: no TPU")
    config = json.loads(
        (ROOT / "bench/configs/qwen1.5-0.5b-w2a16.json").read_text())
    config.update(hidden_size=D, intermediate_size=D * 11 // 4,
                  num_hidden_layers=L, num_attention_heads=H,
                  num_key_value_heads=H, vocab_size=V)
    mix = {"loop": "batch", "slots": B,
           "prompt": {"dist": "uniform", "min": P, "max": P},
           "output": {"dist": "uniform", "min": G, "max": G}}
    small = dataclasses.replace(get_config(config["arch"]), n_layers=L,
                                d_model=D, n_heads=H, n_kv_heads=H,
                                d_ff=D * 11 // 4, vocab_size=V)
    cfg = harness._program(config, mix, small)
    params = lm.quantize_tree(W.make_weights(config, 0), cfg)
    prefill = jax.jit(St.make_prefill_step(cfg, max_len=P + G))
    decode = jax.jit(St.make_decode_step(cfg), donate_argnums=(1,))
    tokens = jnp.zeros((B, P), jnp.int32)

    def decode_steps(logits, caches):
        for i in range(G - 1):
            tok = jnp.argmax(logits[:, -1], -1)[:, None]
            logits, caches = decode(params, caches, {
                "tokens": tok, "pos": jnp.full((B,), P + i, jnp.int32)})
        return jax.block_until_ready(logits)

    decode_steps(*prefill(params, {"tokens": tokens}))   # compiles
    first = jax.block_until_ready(prefill(params, {"tokens": tokens}))
    tracer = harness._Tracer()
    try:
        tracer.start()
        decode_steps(*first)
        tracer.stop()
        (path,) = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                            recursive=True)
        shutil.copyfile(path, out)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
