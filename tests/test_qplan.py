"""Execution-plan subsystem tests: ordered tag->policy rules, component
(not substring) skip matching, group-wise scales, precomputed per-layer
LUTs, and the kernel-backed dense() hot path end to end (dispatch counters,
zero in-jit codebook construction, planned w2a2 logits vs the ref dequant
formulation, checkpoint round-trip of plan nodes)."""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import packing, qlinear, qplan
from repro.core.qlinear import QuantPolicy, QuantizedWeight, dense_serve, \
    dequant_weight, quantize_expert_weight, quantize_weight
from repro.models import lm
from repro.obs import metrics as obs_metrics

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------- #
# Tag matching / skip-list semantics (the substring footgun, ISSUE satellite)
# --------------------------------------------------------------------------- #

def test_skip_matches_components_not_substrings():
    pol = QuantPolicy(w_bits=2, skip=("norm", "embed", "router"))
    # components (and underscore words) that SHOULD be skipped
    assert not pol.applies("final_norm")
    assert not pol.applies("blocks.l0.tok_embed")
    assert not pol.applies("moe.w_router")
    # substring-only overlaps that must NOT be skipped (the old footgun:
    # "norm" in "w_denorm" / "enormous" was True)
    assert pol.applies("mlp.w_denorm")
    assert pol.applies("attn.enormous")
    assert pol.applies("unnormalized")
    # and quantization still applies to ordinary GEMM tags
    assert pol.applies("attn.wq") and pol.applies("mlp.w_up")
    # dotted skip entries keep their multi-component meaning
    dotted = QuantPolicy(w_bits=2, skip=("moe.experts",))
    assert not dotted.applies("blocks.l0.moe.experts.we_gate")
    assert dotted.applies("blocks.l0.mlp.w_up")
    assert dotted.applies("moe.w_router")   # 'moe' alone is not skipped


def test_tag_matches_multi_component_and_wildcard():
    assert qplan.tag_matches("*", "anything.at.all")
    assert qplan.tag_matches("attn.wq", "blocks.l0.attn.wq")
    assert not qplan.tag_matches("attn.wq", "blocks.l0.attn.wk")
    assert not qplan.tag_matches("wq.attn", "blocks.l0.attn.wq")  # order matters
    assert qplan.tag_matches("norm", "x.final_norm")
    assert not qplan.tag_matches("norm", "x.w_denorm")


def test_plan_rules_ordered_first_match_wins():
    attn = QuantPolicy(w_bits=4, kernel="auto")
    rest = QuantPolicy(w_bits=2, a_bits=2, kernel="auto")
    plan = qplan.QuantPlan(rules=(("norm", None), ("attn", attn), ("*", rest)))
    assert plan.policy_for("blocks.l0.attn.wq").w_bits == 4
    assert plan.policy_for("blocks.l0.mlp.w_up").w_bits == 2
    assert plan.policy_for("blocks.l0.ln1.norm") is None
    assert plan.policy_for("final_norm") is None
    # a rule shadowed by an earlier match never fires
    shadow = qplan.QuantPlan(rules=(("*", rest), ("attn", attn)))
    assert shadow.policy_for("attn.wq").w_bits == 2


def test_kernel_bf16_pins_layer_to_full_precision():
    """A policy with kernel='bf16' never applies: quantize_tree must leave
    the weight untouched (not silently run the quantized kernel path)."""
    pol = QuantPolicy(w_bits=2, kernel="bf16")
    assert not pol.applies("attn.wq")
    plan = qplan.QuantPlan(rules=(("attn", pol),
                                  ("*", QuantPolicy(w_bits=2, kernel="auto"))))
    assert plan.policy_for("blocks.l0.attn.wq") is None
    assert plan.policy_for("blocks.l0.mlp.w_up") is not None
    cfg = _smoke_cfg(plan)
    params = lm.init_params(KEY, cfg, mode="plain")
    qp = lm.quantize_tree(params, cfg)
    blk = qp["blocks"]["l0"]
    assert "w" in blk["attn"]["wq"] and "qw" not in blk["attn"]["wq"]
    assert "qw" in blk["mlp"]["w_up"]


def test_expert_rules_resolve_canonical_moe_experts_tag():
    """quantize_tree resolves expert leaves under '...moe.experts.<leaf>',
    the same 'moe.experts' class QAT init resolves — a rule naming it
    covers (or skips) the experts consistently in both phases."""
    cfg0 = reduce_for_smoke(get_config("moonshot-v1-16b-a3b"))
    params = lm.init_params(KEY, cfg0, mode="plain")
    covered = qplan.QuantPlan(rules=(
        ("moe.experts", QuantPolicy(w_bits=2, kernel="auto")), ("*", None)))
    skipped = qplan.QuantPlan(rules=(
        ("experts", None), ("*", QuantPolicy(w_bits=2, kernel="auto"))))
    qp_cov = lm.quantize_tree(params, dataclasses.replace(cfg0, quant=covered))
    qp_skip = lm.quantize_tree(params, dataclasses.replace(cfg0, quant=skipped))
    moe_cov = qp_cov["blocks"]["l0"]["moe"]
    moe_skip = qp_skip["blocks"]["l0"]["moe"]
    assert isinstance(moe_cov["we_gate"], QuantizedWeight)
    assert not isinstance(moe_skip["we_gate"], QuantizedWeight)
    # and the legacy QuantPolicy skip list sees the same class
    legacy = QuantPolicy(w_bits=2, skip=("experts",))
    qp_leg = lm.quantize_tree(params, dataclasses.replace(cfg0, quant=legacy))
    assert not isinstance(qp_leg["blocks"]["l0"]["moe"]["we_gate"],
                          QuantizedWeight)


def test_mixed_expert_projection_plan_dispatches_per_leaf():
    """A plan may cover only SOME expert projections; moe_apply dispatches
    per leaf (kernel for planned, einsum for the rest) instead of assuming
    all three match we_gate."""
    cfg0 = reduce_for_smoke(get_config("moonshot-v1-16b-a3b"))
    params = lm.init_params(KEY, cfg0, mode="plain")
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg0.vocab_size)
    gate_only = qplan.QuantPlan(rules=(
        ("we_gate", QuantPolicy(w_bits=2, kernel="auto")), ("*", None)))
    updown_only = qplan.QuantPlan(rules=(
        ("we_gate", None), ("norm", None), ("embed", None), ("router", None),
        ("*", QuantPolicy(w_bits=2, kernel="auto"))))
    for plan in (gate_only, updown_only):
        cfg = dataclasses.replace(cfg0, quant=plan)
        qp = lm.quantize_tree(params, cfg)
        with obs_metrics.scoped() as reg:
            h, _ = lm.forward(qp, cfg, tokens)
        assert bool(jnp.isfinite(h.astype(jnp.float32)).all())
        assert reg.dispatch_counts().get("expert_dequant_matmul", 0) > 0


def test_make_plan_keeps_sensitive_layers_bf16():
    plan = qplan.make_plan(2, 2, group_size=64)
    for tag in ("tok_embed", "final_norm", "w_router", "lm_head", "pos_embed"):
        assert plan.policy_for(tag) is None, tag
    lp = plan.policy_for("blocks.l0.attn.wq")
    assert (lp.w_bits, lp.a_bits, lp.group_size) == (2, 2, 64)
    assert plan.describe()  # smoke: human-readable table renders


# --------------------------------------------------------------------------- #
# Group-wise quantization format
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [2, 3, 4])
def test_grouped_quantize_weight_roundtrip_bound(bits):
    G = 8 if bits != 3 else 8   # any multiple of the pack factor
    w = jax.random.normal(KEY, (40, 24))     # K=40 pads to 40 (G|40)
    qw = quantize_weight(w, QuantPolicy(w_bits=bits, group_size=G))
    KG = qw.packed.shape[-1] * packing.PACK_FACTOR[bits] // G
    assert qw.scales.shape == (24, KG)
    wd = dequant_weight(qw)
    assert wd.shape == (40, 24)
    # per-element error bounded by the GROUP's scale (finer than per-channel)
    sfull = np.repeat(np.asarray(qw.scales), G, axis=-1)[:, :40].T  # (in, out)
    err = np.abs(np.asarray(w - wd))
    assert (err <= sfull + 1e-6).all()


def test_grouped_strictly_tighter_than_per_channel():
    w = jax.random.normal(KEY, (256, 16))
    per = dequant_weight(quantize_weight(w, QuantPolicy(w_bits=2)))
    grp = dequant_weight(quantize_weight(w, QuantPolicy(w_bits=2, group_size=32)))
    e_per = float(jnp.mean((w - per) ** 2))
    e_grp = float(jnp.mean((w - grp) ** 2))
    assert e_grp < e_per, (e_grp, e_per)


def test_grouped_expert_weight():
    w = jax.random.normal(KEY, (4, 32, 8))      # (E, in, out)
    qw = quantize_expert_weight(w, QuantPolicy(w_bits=2, group_size=16,
                                               kernel="auto"))
    assert qw.scales.shape == (4, 8, 2)
    assert qw.kernel == "dequant_matmul"        # expert LUT GEMM deferred
    wd = dequant_weight(qw)
    assert wd.shape == (4, 32, 8)
    assert float(jnp.abs(w - wd).mean()) < 0.5


def test_k_padding_to_group_multiple():
    w = jax.random.normal(KEY, (20, 8))         # K=20 pads to 32 with G=16
    qw = quantize_weight(w, QuantPolicy(w_bits=2, group_size=16))
    assert qw.packed.shape == (8, 8)            # 32 codes / 4 per byte
    assert qw.scales.shape == (8, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 20))
    y = dense_serve(qw, x, backend="ref")
    want = x @ dequant_weight(qw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# quantize_weight packs what lut_gemm unpacks
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("w_bits,a_bits", [(2, 2), (2, 4), (4, 8)])
def test_quantize_weight_lut_dispatch_matches_ref(w_bits, a_bits):
    """What quantize_weight packs (the natural slot layout) is what
    lut_gemm unpacks: dense_serve through the Pallas LUT route equals its
    shardable dequant formulation on the 'ref' backend."""
    w = jax.random.normal(KEY, (32, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    pol = QuantPolicy(w_bits=w_bits, a_bits=a_bits, kernel="auto")
    qw = quantize_weight(w, pol)
    assert qw.scheme == "a" and qw.kernel == "lut_gemm"
    idx = packing.unpack(qw.packed, w_bits)
    np.testing.assert_array_equal(
        np.asarray(packing.pack(idx, w_bits)), np.asarray(qw.packed))
    y_ref = dense_serve(qw, x, backend="ref")
    y_pal = dense_serve(qw, x, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# The hot path: kernel dispatch + zero in-jit table construction
# --------------------------------------------------------------------------- #

def _smoke_cfg(plan):
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    return dataclasses.replace(cfg, quant=plan)


def test_planned_dense_reaches_kernels_and_precomputes_tables():
    """Acceptance: dense() on a plan-covered layer reaches ops.lut_gemm
    (w2a2) / ops.dequant_matmul (w2a16), with zero product_lut /
    uniform_codebook construction inside the jit'd forward."""
    cfg2 = _smoke_cfg(qplan.get_plan("w2a2"))
    cfg16 = _smoke_cfg(qplan.get_plan("w2a16g64"))
    params = lm.init_params(KEY, cfg2, mode="plain")
    tokens = jax.random.randint(KEY, (2, 24), 0, cfg2.vocab_size)

    qp2 = lm.quantize_tree(params, cfg2)
    qp16 = lm.quantize_tree(params, cfg16)

    def trace(cfg, qp):
        with obs_metrics.scoped() as reg, \
             mock.patch.object(
                qlinear, "product_lut",
                side_effect=AssertionError("product_lut in hot path")), \
             mock.patch.object(
                qlinear.quant, "uniform_codebook",
                side_effect=AssertionError("codebook built in hot path")):
            h = jax.jit(lambda p, t: lm.forward(p, cfg, t)[0])(qp, tokens)
        assert bool(jnp.isfinite(h.astype(jnp.float32)).all())
        return reg.dispatch_counts()

    c2 = trace(cfg2, qp2)
    assert c2.get("lut_gemm", 0) > 0 and c2.get("dequant_matmul", 0) == 0, c2
    c16 = trace(cfg16, qp16)
    assert c16.get("dequant_matmul", 0) > 0 and c16.get("lut_gemm", 0) == 0, c16


def test_legacy_policy_tree_keeps_dequant_einsum_path():
    """A legacy QuantPolicy config must not reach the kernels (bit-for-bit
    compatibility with the historical serving forward)."""
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    assert isinstance(cfg.quant, QuantPolicy) and cfg.quant.kernel is None
    params = lm.init_params(KEY, cfg, mode="plain")
    qp = lm.quantize_tree(params, cfg)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    with obs_metrics.scoped() as reg:
        lm.forward(qp, cfg, tokens)
    assert reg.dispatch_counts() == {}


def test_planned_w2a2_logits_match_ref_formulation():
    """End-to-end: a planned w2a2 qwen1.5-0.5b through the Pallas kernels
    matches the GSPMD-shardable ref dequant formulation within tolerance."""
    cfg_p = _smoke_cfg(qplan.make_plan(2, 2, group_size=32,
                                       backend="pallas_interpret"))
    cfg_r = _smoke_cfg(qplan.make_plan(2, 2, group_size=32, backend="ref"))
    params = lm.init_params(KEY, cfg_p, mode="plain")
    qp = lm.quantize_tree(params, cfg_p)
    tokens = jax.random.randint(KEY, (2, 24), 0, cfg_p.vocab_size)

    def logits(cfg):
        h, _ = lm.forward(qp, cfg, tokens)
        return lm.logits_fn(qp, cfg, h).astype(jnp.float32)

    lp, lr = logits(cfg_p), logits(cfg_r)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lr),
                               rtol=2e-3, atol=2e-3)


def test_mixed_plan_assigns_bits_per_layer_class():
    cfg = _smoke_cfg(qplan.get_plan("mixed_attn4_mlp2"))
    params = lm.init_params(KEY, cfg, mode="plain")
    qp = lm.quantize_tree(params, cfg)
    blk = qp["blocks"]["l0"]
    assert blk["attn"]["wq"]["qw"].bits == 4
    assert blk["attn"]["wq"]["qw"].kernel == "dequant_matmul"
    assert blk["mlp"]["w_up"]["qw"].bits == 2
    assert blk["mlp"]["w_up"]["qw"].kernel == "lut_gemm"
    assert blk["mlp"]["w_up"]["qw"].plut is not None
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    with obs_metrics.scoped() as reg:
        h, _ = lm.forward(qp, cfg, tokens)
    c = reg.dispatch_counts()
    assert c.get("lut_gemm", 0) > 0 and c.get("dequant_matmul", 0) > 0, c
    assert bool(jnp.isfinite(h.astype(jnp.float32)).all())


def test_planned_prefill_decode_consistency():
    """Planned serving keeps the prefill+decode == full-forward invariant
    (kernel outputs are deterministic functions of the same inputs)."""
    cfg = _smoke_cfg(qplan.get_plan("w2a2"))
    params = lm.init_params(KEY, cfg, mode="plain")
    qp = lm.quantize_tree(params, cfg)
    S, B, MAX = 12, 2, 32
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    h_full, _ = lm.forward(qp, cfg, tokens)
    _, pf = lm.forward(qp, cfg, tokens[:, : S - 1], collect_cache=True)
    caches = lm.prefill_to_cache(cfg, pf, S - 1, MAX)
    h_dec, _ = lm.forward(qp, cfg, tokens[:, S - 1: S], caches=caches,
                          pos=jnp.full((B,), S - 1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(h_dec[:, 0]),
                                  np.asarray(h_full[:, -1]))


# --------------------------------------------------------------------------- #
# Checkpoint round-trip of plan nodes (plut / a_levels / group scales)
# --------------------------------------------------------------------------- #

def test_planned_tree_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    cfg = _smoke_cfg(qplan.make_plan(2, 2, group_size=32))
    qparams = lm.quantize_tree(lm.init_params(KEY, cfg, mode="plain"), cfg)
    # the tree actually contains planned leaves with the extra children
    qws = [x for x in jax.tree.leaves(
        qparams, is_leaf=lambda l: isinstance(l, QuantizedWeight))
        if isinstance(x, QuantizedWeight)]
    # grouped scales have one more dim than per-channel would (out, K/G),
    # plus any leading scan-stack dims
    assert qws and all(q.plut is not None and q.group_size == 32 for q in qws)
    save_checkpoint(str(tmp_path / "q"), 1, qparams)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), qparams)
    restored, _, _ = restore_checkpoint(str(tmp_path / "q"), template)
    for a, b in zip(jax.tree.leaves(qparams), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, dtype=np.float32),
                                      np.asarray(b, dtype=np.float32))
    # aux metadata (kernel routing, group size) survives via the template
    rqws = [x for x in jax.tree.leaves(
        restored, is_leaf=lambda l: isinstance(l, QuantizedWeight))
        if isinstance(x, QuantizedWeight)]
    assert rqws[0].kernel == qws[0].kernel
    assert rqws[0].group_size == qws[0].group_size


# --------------------------------------------------------------------------- #
# Planned serving through the engine (prefill + decode on the hot path)
# --------------------------------------------------------------------------- #

def test_engine_serves_planned_model_deterministically():
    from repro.serving import Engine, Request
    cfg = _smoke_cfg(qplan.get_plan("w2a2"))
    params = lm.init_params(KEY, cfg, mode="plain")
    qp = lm.quantize_tree(params, cfg)
    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (int(n),)), np.int32)
               for n in (5, 17, 9)]

    def run_once():
        eng = Engine(cfg, qp, n_slots=2, max_len=64, block_size=8,
                     chunk_size=16)
        reqs = [Request(uid=i, prompt=jnp.asarray(p), max_new=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            assert eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out for r in reqs]

    with obs_metrics.scoped() as reg:
        out1 = run_once()
    assert reg.dispatch_counts().get("lut_gemm", 0) > 0
    out2 = run_once()
    assert out1 == out2        # token-deterministic run-to-run


# --------------------------------------------------------------------------- #
# Bit-sliced route (w{b}a8, kernel='lut_gemm_bitsliced'): plan -> plane
# packing -> registry dispatch -> serving invariants
# --------------------------------------------------------------------------- #

def test_bitsliced_plan_packs_planes_and_dispatches():
    cfg = _smoke_cfg(qplan.make_plan(2, 8, kernel="lut_gemm_bitsliced",
                                     backend="pallas_interpret"))
    params = lm.init_params(KEY, cfg, mode="plain")
    qp = lm.quantize_tree(params, cfg)
    qws = [x for x in jax.tree.leaves(
        qp, is_leaf=lambda l: isinstance(l, QuantizedWeight))
        if isinstance(x, QuantizedWeight)]
    assert qws and all(q.kernel == "lut_gemm_bitsliced" and q.scheme == "bs"
                       for q in qws)
    # bit-plane layout: (..., bits, out, K/4); no product LUT precomputed
    # (the subset-sum LUT is built from activation codes inside the kernel)
    assert all(q.packed.shape[-3] == 2 and q.plut is None for q in qws)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    with obs_metrics.scoped() as reg:
        h, _ = lm.forward(qp, cfg, tokens)
    c = reg.dispatch_counts()
    # bitsliced leaves route through the fused-prologue op (activation
    # quantization happens inside the kernel, not as a separate dispatch)
    assert c.get("lut_gemm_bs_fused", 0) > 0 and c.get("lut_gemm", 0) == 0, c
    assert c.get("lut_gemm_bitsliced", 0) == 0, c
    assert bool(jnp.isfinite(h.astype(jnp.float32)).all())


def test_planned_bitsliced_logits_match_ref_formulation():
    """w2a8 bit-sliced through the Pallas kernel == the GSPMD-shardable ref
    dequant formulation (both sum the same exact integer products)."""
    cfg_p = _smoke_cfg(qplan.make_plan(2, 8, kernel="lut_gemm_bitsliced",
                                       backend="pallas_interpret"))
    cfg_r = _smoke_cfg(qplan.make_plan(2, 8, kernel="lut_gemm_bitsliced",
                                       backend="ref"))
    params = lm.init_params(KEY, cfg_p, mode="plain")
    qp = lm.quantize_tree(params, cfg_p)
    tokens = jax.random.randint(KEY, (2, 24), 0, cfg_p.vocab_size)

    def logits(cfg):
        h, _ = lm.forward(qp, cfg, tokens)
        return lm.logits_fn(qp, cfg, h).astype(jnp.float32)

    lp, lr = logits(cfg_p), logits(cfg_r)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lr),
                               rtol=2e-3, atol=2e-3)


def test_planned_bitsliced_prefill_decode_consistency():
    """The decode step runs the GEMV-specialized (M<=4) kernel grid while
    prefill runs the batched one — same exact integer sums, so the
    prefill+decode == full-forward invariant must hold bit-for-bit."""
    cfg = _smoke_cfg(qplan.make_plan(2, 8, kernel="lut_gemm_bitsliced",
                                     backend="pallas_interpret"))
    params = lm.init_params(KEY, cfg, mode="plain")
    qp = lm.quantize_tree(params, cfg)
    S, B, MAX = 12, 2, 32
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    h_full, _ = lm.forward(qp, cfg, tokens)
    _, pf = lm.forward(qp, cfg, tokens[:, : S - 1], collect_cache=True)
    caches = lm.prefill_to_cache(cfg, pf, S - 1, MAX)
    h_dec, _ = lm.forward(qp, cfg, tokens[:, S - 1: S], caches=caches,
                          pos=jnp.full((B,), S - 1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(h_dec[:, 0]),
                                  np.asarray(h_full[:, -1]))
