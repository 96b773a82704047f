"""Per-kernel correctness: Pallas (interpret=True on CPU) vs pure-jnp ref
across shapes, bitwidths, K grid steps and lookup implementations."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut, packing, quant
from repro.kernels import registry, ref
from repro.kernels.lut_gemm import LANE, SUBLANE, bf16_split, matmul_blocks

RNG = np.random.default_rng(42)


def _codes(shape, bits, rng=None):
    # tests added after the seed suite pass their own rng so the shared
    # draw order (and therefore the seed tests' data) is unchanged
    rng = RNG if rng is None else rng
    return jnp.asarray(rng.integers(0, 2 ** bits, size=shape), dtype=jnp.uint8)


def _lut_operands(M, N, K, bits, rng=None):
    """(M, K) activation codes and (N, K/f) packed weight codes."""
    a_idx = _codes((M, K), bits, rng)
    w_idx = _codes((N, K), bits, rng)
    return a_idx, packing.pack(w_idx, bits)


def _k_steps(M, N, K, bits, group_size, block, scale_align=SUBLANE):
    """Number of K grid steps the packed-weight kernels run for ``block``."""
    bm, bn, bk = block
    return K // matmul_blocks(M, N, K, bits=bits, group_size=group_size,
                              bm=bm, bn=bn, bk=bk,
                              scale_align=scale_align)[2]


# --------------------------------------------------------------------------- #
# lut_gemm (paper-faithful)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [1, 2, 3, 4])
@pytest.mark.parametrize("shape", [(8, 16, 32), (16, 8, 64), (32, 32, 128)])
def test_lut_gemm_matches_ref(bits, shape):
    M, N, K = shape
    a_idx, wp = _lut_operands(M, N, K, bits)
    cb = quant.uniform_codebook(bits, signed=True)
    plut = lut.product_lut(cb, cb)
    want = ref.ref_lut_gemm(a_idx, wp, plut)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                            w_bits=plut.w_bits, a_bits=plut.a_bits,
                            backend="pallas_interpret",
                            block=(min(8, M), min(16, N), min(64, K)))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("bits,K", [(1, 2048), (2, 1024), (4, 512)])
def test_lut_gemm_k_steps_match_ref(bits, K):
    """Accumulation over two K grid steps of (8,128)-legal blocks (a K step
    spans whole lane tiles of packed weight bytes) is exact."""
    M, N = 8, 16
    block = (8, 16, K // 2)
    assert _k_steps(M, N, K, bits, None, block) == 2
    a_idx, wp = _lut_operands(M, N, K, bits, np.random.default_rng(5))
    cb = quant.uniform_codebook(bits, signed=True)
    plut = lut.product_lut(cb, cb)
    want = ref.ref_lut_gemm(a_idx, wp, plut)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                            w_bits=bits, a_bits=bits,
                            backend="pallas_interpret", block=block)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_lut_gemm_nonuniform_float_entries():
    """Paper §5.3: float (non-uniform) LUT entries — signed k-means levels."""
    M, N, K, bits = 8, 8, 32, 2
    a_idx, wp = _lut_operands(M, N, K, bits)
    wl = jnp.asarray([-1.3, -0.2, 0.4, 1.7], jnp.float32)
    al = jnp.asarray([-0.9, -0.1, 0.3, 1.1], jnp.float32)
    plut = lut.product_lut(wl, al)
    want = ref.ref_dequant_gemm(a_idx, wp, wl, al, bits)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                            w_bits=plut.w_bits, a_bits=plut.a_bits,
                            backend="pallas_interpret", block=(8, 8, 32))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


def test_bf16_split_is_exact():
    """Three bf16 parts carry every f32 table entry exactly, so the MXU's
    bf16 operands lose nothing of a wide (w4a8) table row."""
    rng = np.random.default_rng(3)
    x = np.concatenate([rng.integers(-1024, 1025, 512).astype(np.float32),
                        rng.normal(size=512).astype(np.float32) * 1e3])
    parts = np.asarray(bf16_split(jnp.asarray(x.reshape(8, 128))),
                       np.float32)
    assert parts.shape == (24, 128)
    np.testing.assert_array_equal(parts[:8] + parts[8:16] + parts[16:],
                                  x.reshape(8, 128))


def test_lut_gemm_w4a8_matches_ref():
    """Mixed widths (4-bit weights, 8-bit activations): a 4096-entry table
    whose entries need up to 10 significant bits; the sums stay exact."""
    M, N, K = 8, 16, 256
    rng = np.random.default_rng(11)
    a_idx = _codes((M, K), 8, rng)
    wp = packing.pack(_codes((N, K), 4, rng), 4)
    plut = lut.product_lut(quant.uniform_codebook(4, signed=True),
                           quant.uniform_codebook(8, signed=True))
    want = ref.ref_lut_gemm(a_idx, wp, plut)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                            w_bits=4, a_bits=8, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("bits,group", [(2, 16), (2, 32), (2, 64), (4, 32)])
def test_lut_gemm_grouped_scales_match_ref(bits, group):
    """Fused group-scale epilogue vs the grouped oracle, across two K
    tiles (the (K/G, N) scale block spans whole sublane tiles per step)."""
    M, N = 8, 16
    K = 2 * LANE * packing.PACK_FACTOR[bits]
    block = (8, 16, K // 2)
    assert _k_steps(M, N, K, bits, group, block) == 2
    rng = np.random.default_rng(7)
    a_idx, wp = _lut_operands(M, N, K, bits, rng)
    cb = quant.uniform_codebook(bits, signed=True)
    plut = lut.product_lut(cb, cb)
    sc = jnp.asarray(np.abs(rng.normal(size=(N, K // group))) + 0.05,
                     jnp.float32)
    want = ref.ref_lut_gemm(a_idx, wp, plut, w_scales=sc, group_size=group)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, sc,
                            w_bits=bits, a_bits=bits, group_size=group,
                            backend="pallas_interpret", block=block)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_lut_gemm_grouped_equals_scaled_dequant():
    """Group scales in the LUT path == scaling the dequantized weights
    (the plan's accuracy lever is a pure reparametrization)."""
    M, N, K, bits, G = 4, 8, 64, 2, 16
    rng = np.random.default_rng(8)
    a_idx, wp = _lut_operands(M, N, K, bits, rng)
    cb = quant.uniform_codebook(bits, signed=True)
    sc = jnp.asarray(np.abs(rng.normal(size=(N, K // G))) + 0.05, jnp.float32)
    plut = lut.product_lut(cb, cb)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, sc,
                            w_bits=plut.w_bits, a_bits=plut.a_bits,
                            group_size=G, backend="pallas_interpret",
                            block=(4, 8, 64))
    a_deq = jnp.take(cb.levels, a_idx.astype(jnp.int32))
    w_deq = jnp.take(cb.levels, packing.unpack(wp, bits).astype(jnp.int32))
    w_deq = w_deq * jnp.repeat(sc, G, axis=-1)
    want = a_deq @ w_deq.T
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("wb,ab", [(4, 8), (2, 8), (2, 4), (8, 4)])
def test_lut_gemm_asymmetric_bits_match_ref(wb, ab):
    """Mixed operand widths: the table index shifts the weight code by
    a_bits, and K comes from the weight's own pack factor."""
    M, N, K = 8, 16, 64
    rng = np.random.default_rng(11)
    a_idx = _codes((M, K), ab, rng)
    wp = packing.pack(_codes((N, K), wb, rng), wb)
    plut = lut.product_lut(quant.uniform_codebook(wb, signed=True),
                           quant.uniform_codebook(ab, signed=True))
    want = ref.ref_lut_gemm(a_idx, wp, plut)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                            w_bits=wb, a_bits=ab,
                            backend="pallas_interpret", block=(8, 16, 32))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_lut_gemm_asymmetric_grouped_scales():
    M, N, K, wb, ab, G = 8, 8, 128, 4, 8, 32
    rng = np.random.default_rng(12)
    a_idx = _codes((M, K), ab, rng)
    wp = packing.pack(_codes((N, K), wb, rng), wb)
    plut = lut.product_lut(quant.uniform_codebook(wb, signed=True),
                           quant.uniform_codebook(ab, signed=True))
    sc = jnp.asarray(np.abs(rng.normal(size=(N, K // G))) + 0.05, jnp.float32)
    want = ref.ref_lut_gemm(a_idx, wp, plut, w_scales=sc, group_size=G)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, sc,
                            w_bits=wb, a_bits=ab, group_size=G,
                            backend="pallas_interpret", block=(8, 8, 64))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_lut65k_matches_lut16():
    M, N, K, bits = 4, 8, 32, 2
    a_idx, wp = _lut_operands(M, N, K, bits)
    cb = quant.uniform_codebook(bits, signed=True)
    plut = lut.product_lut(cb, cb)
    want = ref.ref_lut_gemm(a_idx, wp, plut)
    t65 = lut.lut65k(cb, cb)
    got = registry.dispatch("lut65k_gemm", packing.pack(a_idx, bits), wp,
                            t65, backend="ref")
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=1e-4)


def test_fused_scale_lut():
    """Scales folded into the table == scaling outside (paper's op fusion)."""
    M, N, K, bits = 4, 8, 32, 2
    a_idx, wp = _lut_operands(M, N, K, bits)
    cb = quant.uniform_codebook(bits, signed=True)
    plain = ref.ref_lut_gemm(a_idx, wp, lut.product_lut(cb, cb))
    fused = ref.ref_lut_gemm(a_idx, wp, lut.fused_lut(cb, cb, 0.25, 0.5))
    np.testing.assert_allclose(np.asarray(plain) * 0.125, np.asarray(fused),
                               rtol=1e-6)


# --------------------------------------------------------------------------- #
# dequant_matmul (TPU-native path)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 16, 32), (16, 32, 128)])
def test_dequant_matmul_matches_ref(bits, dtype, shape):
    M, N, K = shape
    a = jnp.asarray(RNG.normal(size=(M, K)), dtype)
    w_idx = _codes((N, K), bits)
    wp = packing.pack(w_idx, bits)
    cb = quant.uniform_codebook(bits, signed=True)
    scales = jnp.asarray(np.abs(RNG.normal(size=(N,))) + 0.05, jnp.float32)
    want = ref.ref_dequant_matmul(a.astype(jnp.float32), wp, cb.levels,
                                  scales, bits)
    got = registry.dispatch("dequant_matmul", a, wp, cb.levels, scales, bits=bits,
                             backend="pallas_interpret",
                             block=(min(8, M), 16, min(64, K)))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("bits,group", [(2, 16), (2, 64), (4, 32)])
def test_dequant_matmul_grouped_scales_match_ref(bits, group):
    """Group-wise scale formulation (scales fold into the dequantized tile
    before the MXU contraction) vs the grouped oracle."""
    M, N, K = 8, 16, 128
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    wp = packing.pack(_codes((N, K), bits, rng), bits)
    cb = quant.uniform_codebook(bits, signed=True)
    sc = jnp.asarray(np.abs(rng.normal(size=(N, K // group))) + 0.05,
                     jnp.float32)
    want = ref.ref_dequant_matmul(a, wp, cb.levels, sc, bits,
                                  group_size=group)
    got = registry.dispatch("dequant_matmul", a, wp, cb.levels, sc, bits=bits,
                             group_size=group, backend="pallas_interpret",
                             block=(8, 16, 64))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_dequant_matmul_nondivisible_blocks_fit():
    """Block sizes self-adjust to divisors of awkward shapes instead of
    asserting (serving feeds arbitrary (B*S, K) activations)."""
    M, N, K, bits = 6, 24, 40, 2
    rng = np.random.default_rng(10)
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    wp = packing.pack(_codes((N, K), bits, rng), bits)
    cb = quant.uniform_codebook(bits, signed=True)
    sc = jnp.ones((N,), jnp.float32)
    want = ref.ref_dequant_matmul(a, wp, cb.levels, sc, bits)
    got = registry.dispatch("dequant_matmul", a, wp, cb.levels, sc, bits=bits,
                             backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), rtol=1e-4)


@pytest.mark.parametrize("group", [None, 16])
def test_dequant_matmul_grid_accumulation(group):
    """K-grid accumulation across two k steps of (8,128)-legal blocks: a
    step spans whole lane tiles of packed bytes and, grouped, of the
    (N, K/G) scale block."""
    M, N, bits = 16, 16, 2
    K = 1024 if group is None else 2 * LANE * group
    block = (8, 8, K // 2)
    assert _k_steps(M, N, K, bits, group, block, scale_align=LANE) == 2
    rng = np.random.default_rng(13)
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    wp = packing.pack(_codes((N, K), bits, rng), bits)
    cb = quant.uniform_codebook(bits, signed=True)
    sc = jnp.ones((N,), jnp.float32) if group is None else jnp.asarray(
        np.abs(rng.normal(size=(N, K // group))) + 0.05, jnp.float32)
    want = ref.ref_dequant_matmul(a, wp, cb.levels, sc, bits,
                                  group_size=group)
    got = registry.dispatch("dequant_matmul", a, wp, cb.levels, sc, bits=bits,
                             group_size=group, backend="pallas_interpret",
                             block=block)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# expert_dequant_matmul (grouped MoE serving kernel)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("shape", [(4, 8, 16, 32), (2, 16, 32, 128)])
def test_expert_dequant_matmul_matches_ref(bits, shape):
    E, M, N, K = shape
    x = jnp.asarray(RNG.normal(size=(E, M, K)), jnp.float32)
    w_idx = _codes((E, N, K), bits)
    wp = packing.pack(w_idx, bits)
    cb = quant.uniform_codebook(bits, signed=True)
    sc = jnp.asarray(np.abs(RNG.normal(size=(E, N))) + 0.05, jnp.float32)
    want = ref.ref_expert_dequant_matmul(x, wp, cb.levels, sc, bits)
    got = registry.dispatch("expert_dequant_matmul", x, wp, cb.levels, sc, bits=bits,
                                    backend="pallas_interpret",
                                    block=(min(8, M), min(16, N), min(64, K)))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_expert_dequant_matmul_grouped_scales_match_ref():
    E, M, N, K, bits, G = 2, 8, 16, 128, 2, 32
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(E, M, K)), jnp.float32)
    wp = packing.pack(_codes((E, N, K), bits, rng), bits)
    cb = quant.uniform_codebook(bits, signed=True)
    sc = jnp.asarray(np.abs(rng.normal(size=(E, N, K // G))) + 0.05,
                     jnp.float32)
    want = ref.ref_expert_dequant_matmul(x, wp, cb.levels, sc, bits,
                                         group_size=G)
    got = registry.dispatch("expert_dequant_matmul", x, wp, cb.levels, sc, bits=bits,
                                    group_size=G,
                                    backend="pallas_interpret",
                                    block=(8, 16, 64))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_expert_dequant_matmul_nonuniform_codebook():
    E, M, N, K, bits = 2, 8, 16, 64, 2
    x = jnp.asarray(RNG.normal(size=(E, M, K)), jnp.float32)
    wp = packing.pack(_codes((E, N, K), bits), bits)
    cb = jnp.asarray([-1.7, -0.4, 0.3, 1.2], jnp.float32)   # k-means-style
    sc = jnp.ones((E, N), jnp.float32)
    want = ref.ref_expert_dequant_matmul(x, wp, cb, sc, bits)
    got = registry.dispatch("expert_dequant_matmul", x, wp, cb, sc, bits=bits,
                                    backend="pallas_interpret",
                                    block=(8, 16, 64))
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), rtol=1e-4)


# --------------------------------------------------------------------------- #
# kv_cache_attention (packed-cache decode kernel)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("gqa", [(2, 1), (2, 3)])
def test_kv_cache_attention_matches_ref(bits, gqa):
    from repro.models.layers import quantize_kv, quantize_kv4
    B, S, hd = 2, 64, 16
    KV, G = gqa
    q = jnp.asarray(RNG.normal(size=(B, KV, G, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    qf = quantize_kv4 if bits == 4 else quantize_kv
    kp, ksc = qf(k)
    vp, vsc = qf(v)
    lengths = jnp.asarray([S, S // 2], jnp.int32)
    want = ref.ref_kv_cache_attention(q, kp, ksc, vp, vsc, lengths, bits)
    got = registry.dispatch("kv_cache_attention", q, kp, ksc, vp, vsc, lengths, bits=bits,
                                 backend="pallas_interpret", bs=16)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# paged_attention (block-pooled packed-cache decode kernel, serving engine)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("gqa", [(2, 1), (2, 3)])
def test_paged_attention_matches_ref(bits, gqa):
    from repro.models.layers import quantize_kv, quantize_kv4
    KV, G = gqa
    B, hd, bs, n_blocks, nb_max = 3, 16, 8, 12, 4
    q = jnp.asarray(RNG.normal(size=(B, KV, G, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(n_blocks, bs, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(n_blocks, bs, KV, hd)), jnp.float32)
    qf = quantize_kv4 if bits == 4 else quantize_kv
    kp, ksc = qf(k)
    vp, vsc = qf(v)
    # disjoint shuffled tables; unused tail entries point at the null block
    perm = RNG.permutation(np.arange(1, n_blocks))
    lengths = np.asarray([5, 2 * bs + 3, 3 * bs], np.int32)
    tables = np.zeros((B, nb_max), np.int32)
    at = 0
    for b in range(B):
        used = -(-int(lengths[b]) // bs)
        tables[b, :used] = perm[at:at + used]
        at += used
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    want = ref.ref_paged_attention(q, kp, ksc, vp, vsc, tables, lengths, bits)
    got = registry.dispatch("paged_attention", q, kp, ksc, vp, vsc, tables, lengths,
                              bits=bits, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# lut_gemm_bitsliced (T-MAC bit-plane route: per-token subset-sum LUT,
# int16 tile accumulate, GEMV specialization for decode M<=4)
# --------------------------------------------------------------------------- #

def _bitsliced_case(M, N, K, bits, rng, a_bits=8):
    lo = -(1 << (a_bits - 1)) + 1
    a = jnp.asarray(rng.integers(lo, -lo + 1, (M, K)), jnp.int8)
    idx = _codes((N, K), bits, rng)
    planes = packing.pack_bitplanes_signed(idx, bits)
    # int oracle: signed weight codes q = idx - 2^(b-1)
    q = np.asarray(idx, np.int64) - (1 << (bits - 1))
    want = jnp.asarray(np.asarray(a, np.int64) @ q.T, jnp.float32)
    return a, planes, want


@pytest.mark.parametrize("bits", [1, 2, 3, 4])
def test_bitsliced_ref_matches_int_oracle(bits):
    """The plane decomposition re-sums the exact integer products: the ref
    oracle must equal the int64 matmul of signed codes bit-for-bit."""
    rng = np.random.default_rng(20)
    a, planes, want = _bitsliced_case(8, 16, 64, bits, rng)
    got = ref.ref_lut_gemm_bitsliced(a, planes, bits=bits)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_bitplane_pack_roundtrip():
    rng = np.random.default_rng(21)
    for bits in (1, 2, 3, 4):
        idx = _codes((8, 32), bits, rng)
        back = packing.unpack_bitplanes(packing.pack_bitplanes(idx, bits),
                                        bits)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(back))
        backs = packing.unpack_bitplanes_signed(
            packing.pack_bitplanes_signed(idx, bits), bits)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(backs))


@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("M", [1, 4, 8])
def test_bitsliced_pallas_matches_ref(bits, M):
    """Pallas (GEMV grid for M<=4, 3D grid above) vs ref, exact: ungrouped
    outputs are integer sums representable in f32."""
    rng = np.random.default_rng(22)
    a, planes, want = _bitsliced_case(M, 16, 128, bits, rng)
    got = registry.dispatch("lut_gemm_bitsliced", a, planes, None,
                            w_bits=bits, backend="pallas_interpret",
                            block=(min(8, M), 16, 64))   # 2 K-grid steps
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("group", [16, 32])
def test_bitsliced_grouped_scales_match_ref(group):
    """Fused group-scale epilogue vs the grouped oracle. Grouped paths
    differ from the oracle only by f32 summation order -> scaled atol."""
    M, N, K, bits = 4, 16, 128, 2
    rng = np.random.default_rng(23)
    a, planes, _ = _bitsliced_case(M, N, K, bits, rng)
    sc = jnp.asarray(np.abs(rng.normal(size=(N, K // group))) + 0.05,
                     jnp.float32)
    want = ref.ref_lut_gemm_bitsliced(a, planes, sc, bits=bits,
                                      group_size=group)
    got = registry.dispatch("lut_gemm_bitsliced", a, planes, sc,
                            w_bits=bits, group_size=group,
                            backend="pallas_interpret", block=(4, 16, 64))
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(got), rtol=1e-4,
        atol=float(np.abs(np.asarray(want)).max()) * 1e-5)


def test_bitsliced_onehot_lookup_impl():
    """MXU-routed plane lookup (one_hot @ lut) == gather lookup."""
    rng = np.random.default_rng(24)
    a, planes, want = _bitsliced_case(4, 16, 64, 2, rng)
    oneh = registry.dispatch("lut_gemm_bitsliced", a, planes, None,
                             w_bits=2, lookup_impl="onehot",
                             backend="pallas_interpret", block=(4, 16, 64))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(oneh))
