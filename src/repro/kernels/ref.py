"""Pure-jnp reference oracles for every kernel in this package.

Layouts (shared with the Pallas kernels):
  activations  A : (M, K)      packed along K -> (M, K/f)  uint8
  weights      W : (N, K)      packed along K -> (N, K/f)  uint8   ("row per
               output channel" serving layout; GEMM is A @ W^T)
  product LUT    : flat (2^(w_bits+a_bits),)  -- entry [w_idx << a_bits | a_idx]
  out            : (M, N) f32

The oracles are deliberately naive (materialize (M, N, K) where needed); tests
use small shapes. They are the single source of numerical truth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing, quant
from repro.core.lut import ProductLUT


def ref_lut_gemm(
    a_idx: jax.Array,        # (M, K) activation codes
    w_packed: jax.Array,     # (N, K/f) packed weight codes
    lut: ProductLUT,
    w_scales: jax.Array | None = None,
    group_size: int | None = None,
) -> jax.Array:
    """Paper-faithful LUT GEMM: index construction + table lookup + accumulate.
    out[m, n] = sum_k lut[w_idx[n, k] << a_bits | a_idx[m, k]]

    With group-wise weight scales (w_scales (N, K/G), group_size G), each
    K-group's partial sum is scaled before accumulation:
    out[m, n] = sum_g s[n, g] * sum_{k in g} lut[...]."""
    a_idx = a_idx.astype(jnp.int32)
    w_idx = packing.unpack(w_packed, lut.w_bits).astype(jnp.int32)  # (N, K)
    idx = (w_idx[None, :, :] << lut.a_bits) | a_idx[:, None, :]      # (M, N, K)
    prods = jnp.take(lut.table, idx)                                  # (M, N, K)
    if w_scales is None:
        return prods.sum(axis=-1).astype(jnp.float32)
    M, N, K = prods.shape
    pg = prods.reshape(M, N, K // group_size, group_size).sum(axis=-1)
    return (pg * w_scales[None, :, :]).sum(axis=-1).astype(jnp.float32)


def ref_dequant_gemm(
    a_idx: jax.Array,        # (M, K) activation codes
    w_packed: jax.Array,
    w_levels: jax.Array,
    a_levels: jax.Array,
    w_bits: int,
) -> jax.Array:
    """Equivalent computation via explicit dequantize-then-matmul. Must equal
    ref_lut_gemm exactly when products are exactly representable (property
    test)."""
    w_idx = packing.unpack(w_packed, w_bits).astype(jnp.int32)
    a_deq = jnp.take(a_levels, a_idx.astype(jnp.int32))  # (M, K)
    w_deq = jnp.take(w_levels, w_idx)  # (N, K)
    # Same reduction structure as ref_lut_gemm (elementwise products, sum over
    # K last) so the comparison is exact, not just close.
    return (a_deq[:, None, :] * w_deq[None, :, :]).sum(axis=-1).astype(jnp.float32)


def ref_lut65k_gemm(
    a_packed: jax.Array,
    w_packed: jax.Array,
    table: jax.Array,
) -> jax.Array:
    """LUT-65k (paper §3.2): one lookup per 4-element sub-dot-product.
    index = (w_byte << 8) | a_byte. Ref-only on TPU (DESIGN.md §7)."""
    idx = (w_packed[None, :, :].astype(jnp.int32) << 8) | a_packed[:, None, :].astype(jnp.int32)
    return jnp.take(table, idx).sum(axis=-1).astype(jnp.float32)


def ref_dequant_matmul(
    a: jax.Array,
    w_packed: jax.Array,
    codebook: jax.Array,
    scales: jax.Array,
    bits: int,
    group_size: int | None = None,
) -> jax.Array:
    """TPU-native path oracle: unpack -> codebook dequant -> matmul -> scale.

    a: (M, K) float; w_packed: (N, K/f) uint8; codebook: (2^bits,) f32;
    scales: (N,) per-output-channel f32, or (N, K/G) group-wise with
    ``group_size`` set (scales fold into the dequantized weight before the
    contraction — elementwise multiply + dot stays GSPMD-shardable).
    out: (M, N) f32.
    """
    w_idx = packing.unpack(w_packed, bits).astype(jnp.int32)       # (N, K)
    w_deq = jnp.take(codebook, w_idx)                               # (N, K) f32
    if group_size is not None:
        w_deq = w_deq * quant.expand_group_scales(scales, group_size)
        return jnp.dot(a.astype(jnp.float32), w_deq.T)
    out = jnp.dot(a.astype(jnp.float32), w_deq.T)                   # (M, N)
    return out * scales[None, :]


def _bitplane_pattern_matrix(group: int) -> jax.Array:
    """(group, 2^group) int16 with P[j, p] = bit j of pattern p — the matrix
    that turns a group of activation codes into its 2^g subset-sum LUT."""
    p = jnp.arange(2 ** group)
    return jnp.stack([(p >> j) & 1 for j in range(group)]).astype(jnp.int16)


_LUT_LANES = 2  # int16 LUT entries packed per int32 gather word (M >= 2)


def _paired_plane_terms(lut16, w_planes, bits: int, group: int):
    """Fold bit-plane pairs into combined LUTs so one gather covers TWO
    planes (the vector analogue of T-MAC's double-width pshufb).

    For planes (p, p+1) with coefficients (c0, c1) the 2^(2g)-entry table
    clut[..., hi*2^g + lo] = c1*lut16[..., hi] + c0*lut16[..., lo] makes
    clut[idx] with idx = pat[p] | pat[p+1]<<g equal to the two plane
    partials combined — algebraically exact in int16 (|entry| <=
    (|c0|+|c1|) * g * 2^(a_bits-1) <= 12*4*128 for the supported widths).
    Odd ``bits`` leaves one trailing single-plane term. Returns
    [(idx (N, K/g) int32, lut (..., entries) int16, coef_sum), ...].
    """
    coeffs = packing.bitplane_coeffs(bits)
    entries = lut16.shape[-1]
    terms = []
    for p in range(0, bits - 1, 2):
        c0, c1 = int(coeffs[p]), int(coeffs[p + 1])
        clut = (c1 * lut16[..., :, None] + c0 * lut16[..., None, :]) \
            .reshape(*lut16.shape[:-1], entries * entries)
        idx = (w_planes[p].astype(jnp.int32)
               | (w_planes[p + 1].astype(jnp.int32) << group))
        terms.append((idx, clut, abs(c0) + abs(c1)))
    if bits % 2:
        c = int(coeffs[bits - 1])
        terms.append((w_planes[bits - 1].astype(jnp.int32), lut16 * c, abs(c)))
    return terms


def _int16_run(coef_sum: int, group: int, G: int) -> int:
    """Longest pattern run whose int16 partial sums provably cannot
    overflow: run * coef_sum * group * 2^(a_bits-1) < 2^15 with the int8
    code carrier (|code| <= 128), and run must divide G. Returns 1 when no
    run is safe (sum straight in int32). NB the w4 high pair (coef_sum 12)
    bounds runs at 4 — a fixed 16 would overflow at |entry| up to 6144."""
    bound = coef_sum * group * 128
    for run in (32, 16, 8, 4, 2):
        if run * bound < (1 << 15) and G % run == 0:
            return run
    return 1


def ref_lut_gemm_bitsliced(
    a_codes: jax.Array,      # (M, K) int8 SIGNED activation codes
    w_planes: jax.Array,     # (bits, N, K/g) uint8 two's-complement planes
    w_scales: jax.Array | None = None,   # (N, K/G) group-wise weight scales
    *,
    bits: int,
    group: int = packing.BITPLANE_GROUP,
    group_size: int | None = None,
) -> jax.Array:
    """Bit-sliced LUT GEMM oracle (T-MAC decomposition, PAPERS.md).

    The per-token LUT holds subset sums of ``group`` consecutive activation
    codes: lut[m, kg, p] = sum_j bit_j(p) * a[m, kg*g+j] (int16). Bit planes
    are folded pairwise into combined tables (``_paired_plane_terms``) so
    one gather per pattern byte-pair replaces two, and

        out[m, n] = sum_k (idx[n,k] - 2^(b-1)) * a_codes[m, k]

    exactly, in integer arithmetic (exact in f32: |out| < 2^24 for the
    supported widths). With ``w_scales``/``group_size`` each scale-group's
    integer partial is scaled before accumulation, matching the fused
    epilogue of the grouped Pallas kernels.

    This oracle doubles as the compiled CPU serving path (the registry's
    'ref' backend), so the gather is laid out per M regime for XLA:CPU —
    where gathers scalarize and row-major copies dominate:

      M == 1   token-trailing layout: one flat (N*G,) gather from a
               (G*entries, 1) table — the GEMV specialization that beats
               the Eigen bf16 GEMV.
      M >= 2   (ungrouped) LANE PACKING: two adjacent tokens' int16 LUT
               entries share one int32 word, halving gather count again;
               runs of ``_int16_run`` patterns accumulate in int16 before
               widening (overflow-proof by construction).

    Every regime sums the same exact integers, so outputs are bit-identical
    across M — decode rows reproduce the full-forward rows exactly.
    """
    M, K = a_codes.shape
    nplanes, N, G = w_planes.shape
    assert nplanes == bits and G * group == K, (w_planes.shape, a_codes.shape)
    pat = _bitplane_pattern_matrix(group)
    lut16 = jnp.einsum("mgj,jp->mgp",
                       a_codes.reshape(M, G, group).astype(jnp.int16), pat)
    if group_size is not None:
        assert group_size % group == 0 and K % group_size == 0, \
            (K, group_size, group)
        gg = group_size // group           # patterns per scale group
    lanes = group_size is None and M >= 2
    acc = None
    for idx, clut, coef_sum in _paired_plane_terms(lut16, w_planes, bits,
                                                   group):
        entries = clut.shape[-1]
        flat = (idx + (jnp.arange(G) * entries)[None, :]).reshape(-1)  # (N*G,)
        if lanes:
            Mp = M + (M % _LUT_LANES)
            cl = clut if Mp == M else \
                jnp.pad(clut, ((0, Mp - M), (0, 0), (0, 0)))
            packed = jax.lax.bitcast_convert_type(
                cl.transpose(1, 2, 0).reshape(G, entries, Mp // _LUT_LANES,
                                              _LUT_LANES),
                jnp.int32).reshape(G * entries, Mp // _LUT_LANES)
            s = jax.lax.bitcast_convert_type(
                jnp.take(packed, flat, axis=0), jnp.int16).reshape(N, G, Mp)
            run = _int16_run(coef_sum, group, G)
            if run > 1:
                part = (s.reshape(N, G // run, run, Mp)
                        .sum(2, dtype=jnp.int16).sum(1, dtype=jnp.int32))
            else:
                part = s.sum(1, dtype=jnp.int32)
            part = part[:, :M]                                # (N, M)
        else:
            lutT = clut.transpose(1, 2, 0).reshape(G * entries, M)
            s = jnp.take(lutT, flat, axis=0).reshape(N, G, M)
            if group_size is None:
                part = s.sum(1, dtype=jnp.int32)              # (N, M)
            else:
                part = s.reshape(N, G // gg, gg, M).sum(2, dtype=jnp.int32)
        acc = part if acc is None else acc + part
    if group_size is None:
        return acc.T.astype(jnp.float32)                      # (M, N)
    accf = acc.transpose(2, 0, 1).astype(jnp.float32)         # (M, N, K/G)
    return (accf * w_scales[None, :, :].astype(jnp.float32)).sum(-1)


def ref_lut_gemm_bs_fused(
    x: jax.Array,            # (M, K) float activations (bf16/f32)
    w_planes: jax.Array,     # (bits, N, K/g) uint8 two's-complement planes
    w_scales: jax.Array,     # (N,) per-channel | (N, K/G) group-wise
    a_sc: jax.Array | None = None,       # static/explicit activation scale
    *,
    w_bits: int,
    a_bits: int = 8,
    group: int = packing.BITPLANE_GROUP,
    group_size: int | None = None,
) -> jax.Array:
    """Fused-prologue bit-sliced GEMM oracle: quantize the activations
    in-graph with the EXACT ``quant.compute_scale_zero_point`` +
    ``quant.quantize`` ops that ``core.qlinear.dense_serve`` runs two-step
    (same dtype promotion — a bf16 ``x`` keeps a bf16 amax/scale), feed the
    codes to the integer bit-sliced core, and apply the full scale epilogue
    (weight scales x activation scale) instead of returning raw integer
    partials. Per-channel outputs are bitwise identical to the two-step
    route (exact integers + elementwise scaling); group-wise outputs match
    to f32 rounding of the group-scale reduction (XLA may reassociate that
    one f32 sum across lowerings).

    ``a_sc`` short-circuits the in-graph calibration: a (1, 1) static
    per-tensor scale (the leaf's offline-calibrated ``qw.a_sc``) or an
    explicit (M, 1) per-row scale, used as-is.
    """
    if a_sc is not None:
        a_scale = a_sc
    else:
        a_scale, _ = quant.compute_scale_zero_point(
            x, a_bits, signed=True, axis=0)                   # (M, 1)
    aq = quant.quantize(x, a_scale, bits=a_bits, signed=True)
    if group_size is not None:
        y = ref_lut_gemm_bitsliced(aq, w_planes, w_scales, bits=w_bits,
                                   group=group, group_size=group_size)
        return y * a_scale
    y = ref_lut_gemm_bitsliced(aq, w_planes, bits=w_bits, group=group)
    return y * w_scales[None, :] * a_scale


def ref_quantize_pack_act(
    x: jax.Array, scale: jax.Array, bits: int, signed: bool = True
) -> jax.Array:
    """Activation quantize+pack stage (paper Fig. 7 'Quantization'+'Packing').
    Returns packed uint8 codes (..., K/f)."""
    from repro.core import quant
    q = quant.quantize(x, scale, bits=bits, signed=signed)
    idx = quant.to_index(q, bits, signed)
    return packing.pack(idx, bits)


def ref_expert_dequant_matmul(
    x: jax.Array,            # (E, M, K)
    w_packed: jax.Array,     # (E, N, K/f)
    codebook: jax.Array,
    scales: jax.Array,       # (E, N) or (E, N, K/G) group-wise
    bits: int,
    group_size: int | None = None,
) -> jax.Array:
    """Grouped per-expert oracle: out[e] = (x[e] @ dequant(w[e]).T) * sc[e]."""
    w_idx = packing.unpack(w_packed, bits).astype(jnp.int32)    # (E, N, K)
    w_deq = jnp.take(codebook, w_idx)                            # (E, N, K)
    if group_size is not None:
        w_deq = w_deq * quant.expand_group_scales(scales, group_size)
        return jnp.einsum("emk,enk->emn", x.astype(jnp.float32), w_deq)
    out = jnp.einsum("emk,enk->emn", x.astype(jnp.float32), w_deq)
    return out * scales[:, None, :]


def ref_expert_lut_gemm(
    a_idx: jax.Array,        # (E, M, K) per-expert activation codes
    w_packed: jax.Array,     # (E, N, K/fw)
    lut: ProductLUT,
    w_scales: jax.Array | None = None,   # (E, N, K/G) group-wise
    group_size: int | None = None,
) -> jax.Array:
    """Grouped per-expert LUT GEMM oracle: ``ref_lut_gemm`` vmapped over the
    expert axis. out[e, m, n] = sum_k lut[w_idx[e,n,k] << a_bits | a_idx[e,m,k]]
    (per K-group scaled before accumulation when ``w_scales`` is given)."""
    if w_scales is None:
        return jax.vmap(lambda a, w: ref_lut_gemm(a, w, lut))(a_idx, w_packed)
    return jax.vmap(lambda a, w, s: ref_lut_gemm(
        a, w, lut, w_scales=s, group_size=group_size))(
            a_idx, w_packed, w_scales)


def ref_kv_cache_attention(
    q: jax.Array,            # (B, KV, G, hd)
    k_packed: jax.Array,     # (B, S, KV, hd/f)
    k_sc: jax.Array,         # (B, S, KV)
    v_packed: jax.Array,
    v_sc: jax.Array,
    lengths: jax.Array,      # (B,)
    bits: int,
) -> jax.Array:
    """Oracle: dequantize the whole cache, masked softmax attention."""
    if bits == 4:
        kd = (packing.unpack(k_packed, 4).astype(jnp.float32) - 8.0) * k_sc[..., None]
        vd = (packing.unpack(v_packed, 4).astype(jnp.float32) - 8.0) * v_sc[..., None]
    else:
        kd = k_packed.astype(jnp.float32) * k_sc[..., None]
        vd = v_packed.astype(jnp.float32) * v_sc[..., None]
    hd = q.shape[-1]
    s = jnp.einsum("begh,bseh->begs", q.astype(jnp.float32), kd) * hd ** -0.5
    mask = jnp.arange(kd.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("begs,bseh->begh", p, vd)


def ref_paged_attention(
    q: jax.Array,             # (B, KV, G, hd)
    k_pool: jax.Array,        # (n_blocks, bs, KV, hd/f)
    k_sc: jax.Array,          # (n_blocks, bs, KV)
    v_pool: jax.Array,
    v_sc: jax.Array,
    block_tables: jax.Array,  # (B, nb_max)
    lengths: jax.Array,       # (B,)
    bits: int,
) -> jax.Array:
    """Oracle: gather each sequence's blocks into a dense view, then run the
    flat packed-cache attention oracle over it."""
    B, nb = block_tables.shape
    bs = k_pool.shape[1]

    def view(pool):
        g = pool[block_tables]                      # (B, nb, bs, ...)
        return g.reshape(B, nb * bs, *pool.shape[2:])

    return ref_kv_cache_attention(q, view(k_pool), view(k_sc),
                                  view(v_pool), view(v_sc), lengths, bits)


def ref_paged_attention_splitkv(
    q: jax.Array,             # (B, KV, G, hd)
    k_pool: jax.Array,        # (n_blocks, bs, KV, hd/f)
    k_sc: jax.Array,          # (n_blocks, bs, KV)
    v_pool: jax.Array,
    v_sc: jax.Array,
    block_tables: jax.Array,  # (B, nb_max)
    lengths: jax.Array,       # (B,)
    bits: int,
    kv_splits: int = 2,
) -> jax.Array:
    """Oracle for the flash-decoding split: partition each table into
    ``kv_splits`` chunks, compute per-chunk unnormalized partials (acc, m, l)
    with plain jnp, and merge exactly — the same (max, sumexp) lse algebra as
    ``kernels.paged_attention.merge_splitkv_partials``, kept standalone here
    so the oracle shares no code with the lowering it checks."""
    B, nb = block_tables.shape
    bs = k_pool.shape[1]
    ns = max(1, min(int(kv_splits), nb))
    nbc = -(-nb // ns)
    tbl = jnp.pad(block_tables, ((0, 0), (0, ns * nbc - nb)))

    if bits == 4:
        def dq(pool, sc):
            u = packing.unpack(pool, 4).astype(jnp.float32)
            return (u - 8.0) * sc[..., None]
    else:
        def dq(pool, sc):
            return pool.astype(jnp.float32) * sc[..., None]

    hd = q.shape[-1]
    qf = q.astype(jnp.float32)
    o_parts, m_parts, l_parts = [], [], []
    for c in range(ns):
        ids = tbl[:, c * nbc:(c + 1) * nbc]         # (B, nbc)
        kd = dq(k_pool[ids], k_sc[ids]).reshape(B, nbc * bs, *k_pool.shape[2:-1], -1)
        vd = dq(v_pool[ids], v_sc[ids]).reshape(B, nbc * bs, *v_pool.shape[2:-1], -1)
        s = jnp.einsum("begh,bseh->begs", qf, kd) * hd ** -0.5
        pos = c * nbc * bs + jnp.arange(nbc * bs)
        mask = pos[None, :] < lengths[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        m_c = s.max(-1)                             # (B, KV, G)
        p = jnp.exp(s - m_c[..., None])
        o_parts.append(jnp.einsum("begs,bseh->begh", p, vd))
        m_parts.append(m_c)
        l_parts.append(p.sum(-1))
    o = jnp.stack(o_parts, axis=1)                  # (B, ns, KV, G, hd)
    m = jnp.stack(m_parts, axis=1)                  # (B, ns, KV, G)
    ll = jnp.stack(l_parts, axis=1)
    M = m.max(axis=1)
    w = jnp.exp(m - M[:, None])
    num = (o * w[..., None]).sum(axis=1)
    den = (ll * w).sum(axis=1)
    return num / jnp.maximum(den, 1e-30)[..., None]
