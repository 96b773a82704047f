"""Activation-quantized (w{b}a{b}) expert LUT GEMM for the MoE path.

The ref oracle (`ref_expert_lut_gemm`) is the single source of truth; the
Pallas kernel (interpret mode) and the planned MoE forward are checked
against it and against the algebraically-identical dequant formulation.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.core import packing, qplan, quant
from repro.core.lut import product_lut
from repro.core.qlinear import QuantPolicy, QuantizedWeight, quantize_expert_weight
from repro.kernels import registry as kops
from repro.kernels import ref as R
from repro.models import lm
from repro.obs import metrics as obs_metrics


def _codes(rng, shape, bits):
    return jnp.asarray(rng.integers(0, 2 ** bits, shape), jnp.uint8)


def test_expert_lut_oracle_equals_dequant_formulation():
    rng = np.random.default_rng(0)
    E, M, N, K, b = 3, 4, 6, 16, 2
    lv = quant.uniform_codebook(b, True).levels
    lut = product_lut(lv, lv)
    a_idx, w_idx = _codes(rng, (E, M, K), b), _codes(rng, (E, N, K), b)
    got = R.ref_expert_lut_gemm(a_idx, packing.pack(w_idx, b), lut)
    a_deq = jnp.take(lv, a_idx.astype(jnp.int32))
    w_deq = jnp.take(lv, w_idx.astype(jnp.int32))
    want = jnp.einsum("emk,enk->emn", a_deq, w_deq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_expert_lut_pallas_matches_oracle_grouped_and_not():
    rng = np.random.default_rng(1)
    E, M, N, K, b, G = 2, 4, 8, 32, 2, 8
    lv = quant.uniform_codebook(b, True).levels
    lut = product_lut(lv, lv)
    a_idx = _codes(rng, (E, M, K), b)
    wp = packing.pack(_codes(rng, (E, N, K), b), b)
    sc = jnp.asarray(rng.random((E, N, K // G)), jnp.float32)
    for w_scales, group in ((None, None), (sc, G)):
        want = R.ref_expert_lut_gemm(a_idx, wp, lut, w_scales=w_scales,
                                     group_size=group)
        got = kops.dispatch("expert_lut_gemm", a_idx, wp, lut.table,
                            w_scales, w_bits=lut.w_bits, a_bits=lut.a_bits,
                            group_size=group, backend="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


def test_quantize_expert_weight_keeps_lut_route():
    """A w{b}a{b} plan no longer downgrades experts to dequant_matmul: the
    packed leaf keeps kernel='lut_gemm' with the precomputed tables."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((3, 16, 8)), jnp.float32)
    pol = QuantPolicy(w_bits=2, a_bits=2, kernel="auto")
    qw = quantize_expert_weight(w, pol)
    assert qw.kernel == "lut_gemm"
    assert qw.a_bits == 2 and qw.a_levels is not None and qw.plut is not None


def _moe_setup(plan):
    cfg = reduce_for_smoke(get_config("moonshot-v1-16b-a3b"))
    cfg = dataclasses.replace(cfg, quant=plan)
    params = lm.init_params(jax.random.PRNGKey(0), cfg, mode="plain")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def test_moe_w2a2_dispatches_expert_lut_and_matches_ref():
    """Planned w2a2 MoE forward reaches expert_lut_gemm (dispatch counter)
    and the interpret-mode kernel path equals the 'ref' dequant formulation
    of the same quantized model."""
    plan = qplan.get_plan("w2a2")
    cfg, params, tokens = _moe_setup(plan)
    qparams = lm.quantize_tree(params, cfg)
    leaves = [l for l in jax.tree.leaves(
                  qparams, is_leaf=lambda x: isinstance(x, QuantizedWeight))
              if isinstance(l, QuantizedWeight)]
    assert any(l.kernel == "lut_gemm" and l.a_bits is not None
               and l.packed.ndim >= 3 for l in leaves)

    with obs_metrics.scoped() as reg:
        h, _ = lm.forward(qparams, cfg, tokens)
        logits = lm.logits_fn(qparams, cfg, h).astype(jnp.float32)
    assert reg.dispatch_counts().get("expert_lut_gemm", 0) > 0, \
        reg.dispatch_counts()

    ref_cfg = dataclasses.replace(
        cfg, quant=dataclasses.replace(plan, backend="ref"))
    h2, _ = lm.forward(qparams, ref_cfg, tokens)
    logits2 = lm.logits_fn(qparams, ref_cfg, h2).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2),
                               atol=2e-2, rtol=2e-2)


def test_moe_w2a2_grouped_expert_lut_matches_ref():
    plan = qplan.get_plan("w2a2g64")
    cfg, params, tokens = _moe_setup(plan)
    qparams = lm.quantize_tree(params, cfg)
    with obs_metrics.scoped() as reg:
        h, _ = lm.forward(qparams, cfg, tokens)
    assert reg.dispatch_counts().get("expert_lut_gemm", 0) > 0
    ref_cfg = dataclasses.replace(
        cfg, quant=dataclasses.replace(plan, backend="ref"))
    h2, _ = lm.forward(qparams, ref_cfg, tokens)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h2, np.float32),
                               atol=2e-2, rtol=2e-2)
