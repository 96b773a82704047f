"""End-to-end system behaviour: serving conv path (the paper's operator),
roofline HLO parser validated against XLA cost_analysis on unrolled models,
checkpointing packed trees, config registry integrity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import roofline as RL
from repro.configs import SHAPES, get_config, reduce_for_smoke
from repro.core import conv, qlinear
from repro.core.qlinear import QuantPolicy

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------- #
# CNN operator path (paper §5.1/5.2)
# --------------------------------------------------------------------------- #

def test_conv2d_lut_serve_matches_dequant():
    x = jax.random.normal(KEY, (2, 8, 8, 4), jnp.float32)
    p = conv.conv2d_init(jax.random.PRNGKey(1), 3, 3, 4, 8)
    y_plain = conv.conv2d_apply(p, x)
    qw = qlinear.quantize_weight(p["w"], QuantPolicy(w_bits=2, a_bits=2))
    y_lut = conv.conv2d_serve(qw, x, 3, 3, a_bits=2, backend="ref")
    assert y_lut.shape == y_plain.shape
    # 2-bit quantization error is large but bounded and finite
    assert bool(jnp.isfinite(y_lut).all())
    rel = float(jnp.abs(y_lut - y_plain).mean() / jnp.abs(y_plain).mean())
    assert rel < 1.0, rel


def test_conv_gemm_shape_labels():
    M, N, K = conv.conv_gemm_shape((1, 56, 56, 64), 3, 3, 128, stride=1)
    assert (M, N, K) == (1 * 56 * 56, 3 * 3 * 64, 128)


# --------------------------------------------------------------------------- #
# Roofline HLO parser
# --------------------------------------------------------------------------- #

def test_parser_counts_scan_trip_counts():
    """The motivating case: scan of N matmuls == N x unrolled flops."""
    def body(x, w):
        return jnp.tanh(x @ w), None

    def f_scan(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y

    def f_unroll(x, ws):
        for i in range(8):
            x, _ = body(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    hlo_s = jax.jit(f_scan).lower(x, ws).compile().as_text()
    c_u = jax.jit(f_unroll).lower(x, ws).compile()
    stats = RL.parse_hlo(hlo_s)
    want = c_u.cost_analysis()["flops"]
    assert stats.unknown_trip_counts == 0
    np.testing.assert_allclose(stats.dot_flops, want, rtol=0.02)


def test_parser_vs_cost_analysis_on_unrolled_model():
    """On a model with NO scans (unrolled reduced config), parser dot-flops
    must agree with XLA cost_analysis to within elementwise-op noise."""
    from repro.models import lm
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    cfg = dataclasses.replace(cfg, remat="none")
    params = lm.init_params(KEY, cfg, mode="plain")
    tokens = jnp.ones((2, 32), jnp.int32)

    def fwd(p, t):
        h, _ = lm.forward(p, cfg, t)
        return lm.chunked_ce_loss(p, cfg, h, t)

    compiled = jax.jit(fwd).lower(params, tokens).compile()
    stats = RL.parse_hlo(compiled.as_text())
    xla = compiled.cost_analysis()["flops"]
    # single superblock: the layer scan has trip 1; chunk scans also 1
    assert stats.dot_flops <= xla * 1.05
    assert stats.dot_flops >= 0.5 * xla, (stats.dot_flops, xla)


def test_shape_bytes():
    assert RL.shape_bytes("f32[16,4096,1024]{2,1,0}") == 16 * 4096 * 1024 * 4
    assert RL.shape_bytes("(bf16[8,8]{1,0}, s8[4]{0})") == 128 + 4
    assert RL.shape_bytes("pred[]") == 1


def test_model_flops_accounting():
    cfg = get_config("llama4-maverick-400b-a17b")
    total, active = cfg.n_params(), cfg.n_active_params()
    assert 3.5e11 < total < 4.5e11, total     # ~400B
    assert 1.1e10 < active < 2.2e10, active   # ~17B
    cfg2 = get_config("codeqwen1.5-7b")
    assert 6e9 < cfg2.n_params() < 8.5e9


# --------------------------------------------------------------------------- #
# Registry / checkpoint of packed trees
# --------------------------------------------------------------------------- #

def test_all_archs_registered_with_exact_figures():
    figures = {
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163840),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
    }
    for arch, (L, D, H, KV, F, V) in figures.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, D, H, KV, F, V), arch


def test_moe_structure():
    m = get_config("moonshot-v1-16b-a3b").moe
    assert (m.n_experts, m.top_k) == (64, 6)
    l4 = get_config("llama4-maverick-400b-a17b")
    assert (l4.moe.n_experts, l4.moe.top_k) == (128, 1)
    assert l4.moe_pattern == (False, True)        # MoE interleave


def test_checkpoint_packed_tree(tmp_path):
    """QuantizedWeight trees checkpoint and restore through keyed paths."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.models import lm
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    qparams = lm.quantize_tree(lm.init_params(KEY, cfg, mode="plain"), cfg)
    save_checkpoint(str(tmp_path / "q"), 1, qparams)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), qparams)
    restored, _, _ = restore_checkpoint(str(tmp_path / "q"), template)
    for a, b in zip(jax.tree.leaves(qparams), jax.tree.leaves(restored)):
        assert jnp.asarray(b).dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(a, dtype=np.float32),
                                      np.asarray(b, dtype=np.float32))


def test_long_context_policy():
    from repro.configs import LONG_CONTEXT_OK, cell_is_runnable
    assert "rwkv6-1.6b" in LONG_CONTEXT_OK
    ok, why = cell_is_runnable(get_config("codeqwen1.5-7b"), SHAPES["long_500k"])
    assert not ok and "full-attention" in why
    ok, _ = cell_is_runnable(get_config("gemma3-12b"), SHAPES["long_500k"])
    assert ok
