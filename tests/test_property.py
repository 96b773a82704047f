"""Hypothesis property tests on the system's invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import lut, packing, quant
from repro.dist import collectives
from repro.kernels import ref

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

bits_st = st.sampled_from([1, 2, 3, 4])


@given(bits=bits_st, rows=st.integers(1, 5), groups=st.integers(1, 8),
       seed=st.integers(0, 2 ** 16))
def test_pack_unpack_roundtrip(bits, rows, groups, seed):
    f = packing.PACK_FACTOR[bits]
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 2 ** bits, (rows, groups * f)), jnp.uint8)
    packed = packing.pack(idx, bits)
    assert packed.shape == (rows, groups)
    np.testing.assert_array_equal(np.asarray(packing.unpack(packed, bits)),
                                  np.asarray(idx))
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_paired(packed, bits)), np.asarray(idx))


@given(bits=st.sampled_from([2, 3, 4]), rows=st.integers(1, 4),
       groups=st.integers(1, 6), seed=st.integers(0, 2 ** 16),
       scheme=st.sampled_from(["a", "c", "d"]))
def test_pack_roundtrip_across_schemes(bits, rows, groups, seed, scheme):
    """quantize-time packing is byte-identical across schemes 'a'/'c'/'d'
    (pack_indexready IS pack), so every scheme round-trips through the
    natural unpack AND honours the scheme's unpack contract."""
    f = packing.PACK_FACTOR[bits]
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 2 ** bits, (rows, groups * f)), jnp.uint8)
    packer = packing.pack_indexready if scheme in ("c", "d") else packing.pack
    packed = packer(idx, bits)
    np.testing.assert_array_equal(np.asarray(packing.pack(idx, bits)),
                                  np.asarray(packed))     # byte identity
    np.testing.assert_array_equal(np.asarray(packing.unpack(packed, bits)),
                                  np.asarray(idx))        # natural roundtrip
    got = packing.UNPACK_SCHEMES[scheme](packed, bits)
    want = (idx.astype(jnp.int32) << bits) if scheme in ("c", "d") else idx
    np.testing.assert_array_equal(np.asarray(got, np.int32) & 0xFF,
                                  np.asarray(want, np.int32) & 0xFF)


@given(bits=st.sampled_from([2, 3, 4]), out=st.integers(1, 6),
       kg=st.integers(1, 4), seed=st.integers(0, 2 ** 16))
def test_groupwise_scale_reshape_roundtrip(bits, out, kg, seed):
    """Group-wise quantize_weight: scales shape (out, K/G), dequant equals
    the manual codebook-gather x repeated-scale expansion, and the error is
    bounded by each element's GROUP scale."""
    from repro.core.qlinear import QuantPolicy, dequant_weight, quantize_weight
    G = 2 * packing.PACK_FACTOR[bits]
    K = kg * G
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(K, out)) * 2.0, jnp.float32)
    qw = quantize_weight(w, QuantPolicy(w_bits=bits, group_size=G))
    assert qw.scales.shape == (out, kg)
    # manual expansion: take(codebook, unpack) * repeat(scales, G)
    idx = packing.unpack(qw.packed, bits).astype(jnp.int32)
    manual = (jnp.take(qw.codebook, idx)
              * jnp.repeat(qw.scales, G, axis=-1))[:, :K].T
    np.testing.assert_array_equal(np.asarray(dequant_weight(qw)),
                                  np.asarray(manual))
    err = np.abs(np.asarray(w) - np.asarray(manual))
    bound = np.repeat(np.asarray(qw.scales), G, axis=-1).T + 1e-6
    assert (err <= bound).all()


@given(bits=st.sampled_from([1, 2, 3, 4]), seed=st.integers(0, 2 ** 16))
def test_indexready_contract(bits, seed):
    """unpack_indexready(pack_indexready(w)) == w << bits (scheme 'c'/'d')."""
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 2 ** bits, (3, 4 * packing.PACK_FACTOR[bits])),
                      jnp.uint8)
    got = packing.unpack_indexready(packing.pack_indexready(idx, bits), bits)
    want = (idx.astype(jnp.int32) << bits) & 0xFF
    np.testing.assert_array_equal(np.asarray(got, np.int32) & 0xFF,
                                  np.asarray(want))


@given(bits=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2 ** 16))
def test_pack_words_roundtrip(bits, seed):
    f = 32 // bits
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 2 ** bits, (2, 2 * f)), jnp.uint8)
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_words(packing.pack_words(idx, bits), bits)),
        np.asarray(idx))


@given(bits=bits_st, m=st.integers(1, 6), n=st.integers(1, 6),
       kg=st.integers(1, 4), seed=st.integers(0, 2 ** 16),
       signed=st.booleans())
def test_lut_gemm_equals_dequant_gemm_exactly(bits, m, n, kg, seed, signed):
    """The paper's central claim: table lookup == multiply, exactly, for any
    integer codebook (products are integers, f32-exact)."""
    f = packing.PACK_FACTOR[bits]
    K = kg * f
    rng = np.random.default_rng(seed)
    a_idx = jnp.asarray(rng.integers(0, 2 ** bits, (m, K)), jnp.uint8)
    wp = packing.pack(jnp.asarray(rng.integers(0, 2 ** bits, (n, K)), jnp.uint8), bits)
    cb = quant.uniform_codebook(bits, signed)
    got = ref.ref_lut_gemm(a_idx, wp, lut.product_lut(cb, cb))
    want = ref.ref_dequant_gemm(a_idx, wp, cb.levels, cb.levels, bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(bits=st.sampled_from([2, 3, 4, 8]), seed=st.integers(0, 2 ** 16),
       signed=st.booleans())
def test_quantize_error_bound(bits, seed, signed):
    """|x - dequant(quantize(x))| <= scale/2 inside the clip range."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)) * 2.0, jnp.float32)
    scale, zp = quant.compute_scale_zero_point(x, bits, signed=signed,
                                               symmetric=signed)
    q = quant.quantize(x, scale, zp, bits=bits, signed=signed)
    xr = quant.dequantize(q, scale, zp)
    qmin, qmax = quant.qrange(bits, signed)
    lo = float((qmin - np.asarray(zp)) * np.asarray(scale))
    hi = float((qmax - np.asarray(zp)) * np.asarray(scale))
    inside = (np.asarray(x) >= lo) & (np.asarray(x) <= hi)
    err = np.abs(np.asarray(x) - np.asarray(xr))[inside]
    assert err.size == 0 or err.max() <= float(np.max(scale)) / 2 + 1e-6


@given(seed=st.integers(0, 2 ** 16))
def test_to_index_from_index_roundtrip(seed):
    rng = np.random.default_rng(seed)
    for bits in (1, 2, 3, 4, 8):
        for signed in (True, False):
            qmin, qmax = quant.qrange(bits, signed)
            q = jnp.asarray(rng.integers(qmin, qmax + 1, (32,)), jnp.int8)
            idx = quant.to_index(q, bits, signed)
            assert int(idx.max()) < 2 ** bits and int(idx.min()) >= 0
            np.testing.assert_array_equal(
                np.asarray(quant.from_index(idx, bits, signed)), np.asarray(q))


@given(seed=st.integers(0, 2 ** 16), n=st.integers(1, 600))
def test_int8_blockwise_roundtrip_bound(seed, n):
    """Gradient-compression codec: |x - dq(q(x))| <= blockmax/127 halves."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n,)) * 3.0, jnp.float32)
    q, sc = collectives.quantize_int8_blockwise(x)
    xr = collectives.dequantize_int8_blockwise(q, sc, x.shape)
    err = np.abs(np.asarray(x - xr))
    bound = np.repeat(np.asarray(sc), collectives._BLOCK)[: n] * 0.5 + 1e-7
    assert (err <= bound).all()


@given(seed=st.integers(0, 2 ** 16))
def test_codebook_quantize_nearest(seed):
    rng = np.random.default_rng(seed)
    cb = quant.Codebook(jnp.sort(jnp.asarray(rng.normal(size=(8,)), jnp.float32)))
    x = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    idx = quant.codebook_quantize(x, cb)
    xr = quant.codebook_dequantize(idx, cb)
    # nearest-level: no other level is closer
    d_chosen = np.abs(np.asarray(x - xr))
    d_all = np.abs(np.asarray(x)[:, None] - np.asarray(cb.levels)[None, :])
    assert np.allclose(d_chosen, d_all.min(-1), atol=1e-6)


@given(seed=st.integers(0, 2 ** 16), b=st.integers(1, 3), s=st.integers(1, 5))
def test_ring_fold_matches_ring_update(seed, b, s):
    """prefill_to_cache ring layout == incremental _ring_update writes."""
    from repro.models.layers import _ring_update
    from repro.models import lm as LM
    from repro.configs import get_config, reduce_for_smoke
    cfg = reduce_for_smoke(get_config("h2o-danube-3-4b"))
    W = cfg.window
    S = s + 3
    rng = np.random.default_rng(seed)
    kv = jnp.asarray(rng.normal(size=(b, S, 2, 4)), jnp.float32)
    # incremental
    ring = jnp.zeros((b, W, 2, 4), jnp.float32)
    for t in range(S):
        ring = _ring_update(ring, kv[:, t:t + 1], jnp.full((b,), t, jnp.int32), W)
    # fold (via the module-private helper path)
    caches = {"blocks": {"l0": {"attn": {"k": kv, "v": kv}}}}
    folded = LM.prefill_to_cache(cfg, caches, S, W)["blocks"]["l0"]["attn"]["k"]
    L = min(S, W)
    # compare only the valid slots
    valid_slots = sorted((t % W) for t in range(max(0, S - W), S))
    np.testing.assert_allclose(np.asarray(folded[:, valid_slots]),
                               np.asarray(ring[:, valid_slots]), atol=1e-6)
