"""Bit-sliced LUT GEMM as a Pallas TPU kernel (T-MAC decomposition).

Where the paper's LUT-16 kernel (lut_gemm.py) precomputes a *product* LUT
over (w_level, a_level) pairs offline, the bit-sliced variant builds a tiny
LUT from the *activations themselves* at run time and slices the weights
into one-bit planes:

  VMEM:  one (bm x bk) int8 activation-code tile, the (bits x bn x bk/g)
         weight plane-pattern tile, one (bm x bn) f32 accumulator
  VPU:   LUT build — g doubling steps turn the activation tile into a
         (bm, bk/g, 2^g) table of group subset sums (int16); one gather per
         plane replaces g multiply-accumulates (pshufb in T-MAC's AVX2
         kernels, a vector gather here); plane partials combine with the
         two's-complement coefficients (1, ..., -2^(b-1)).

Accumulation is int16 inside a tile wherever the worst-case magnitude
bound (bk * 2^(a_bits-1), or group_size * 2^(a_bits-1) for the fused
group-scale path) provably fits, and widens to f32 only in the epilogue —
the T-MAC trick that keeps the inner loop in 16-bit lanes.

Decode shapes get their own tiling: for M <= 4 (the serving hot loop is
batched decode, not M=64 GEMM) the kernel drops the M grid axis entirely,
holds all M rows in one block, and walks a 2D (N, K) grid with wider N
tiles — the GEMV specialization.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import packing
from .lut_gemm import LANE, SUBLANE, _fit, _shrink

GEMV_ROWS = 4  # M <= GEMV_ROWS routes to the decode (GEMV) tiling


def _group_lut(a_tile: jax.Array, group: int) -> jax.Array:
    """(bm, bk) int8 codes -> (bm, bk/g, 2^g) int16 subset-sum LUT.

    Iterative doubling: after step j the last axis holds all subset sums of
    the first j+1 codes in each group, so lut[..., p] = sum_j bit_j(p)*a_j.
    g shift-adds total — cheaper than the 2^g naive fill.
    """
    bm, bk = a_tile.shape
    g = a_tile.reshape(bm, bk // group, group).astype(jnp.int16)
    lut = jnp.zeros((bm, bk // group, 1), jnp.int16)
    for j in range(group):
        lut = jnp.concatenate([lut, lut + g[..., j:j + 1]], axis=-1)
    return lut


def _plane_lookup(lut: jax.Array, pat: jax.Array, lookup_impl: str) -> jax.Array:
    """Gather each weight pattern's subset sum: (bm, bk/g, entries) LUT x
    (bn, bk/g) patterns -> (bm, bn, bk/g). 'take' is the vector-gather port
    of pshufb; 'onehot' routes the lookup through the MXU (f32)."""
    bm, bkg, entries = lut.shape
    if lookup_impl == "onehot":
        oh = jax.nn.one_hot(pat.astype(jnp.int32), entries, dtype=jnp.float32)
        return jnp.einsum("ngp,mgp->mng", oh, lut.astype(jnp.float32))
    lutf = lut.reshape(bm, bkg * entries)
    offs = jax.lax.broadcasted_iota(jnp.int32, pat.shape, 1) * entries
    return jnp.take(lutf, pat.astype(jnp.int32) + offs, axis=1)


def _paired_tile_luts(lut, planes, bits: int, group: int):
    """Fold bit-plane pairs into combined LUTs (ref._paired_plane_terms, the
    tile-local form): planes (p, p+1) with coefficients (c0, c1) become ONE
    2^(2g)-entry table clut[..., hi*2^g + lo] = c1*lut[hi] + c0*lut[lo],
    indexed by pat[p] | pat[p+1]<<g — one gather amortizes both planes'
    doubling steps. Odd ``bits`` leaves a trailing single-plane term.
    Yields (idx (bn, bk/g) int32, clut (bm, bk/g, entries) int16, coef_sum)."""
    from repro.kernels.ref import _paired_plane_terms
    return _paired_plane_terms(lut, planes, bits, group)


def _plane_partials(a, planes, *, bits, group, a_bits, lookup_impl,
                    part_len):
    """Shared tile body: build the LUT, fold plane pairs into combined
    tables (coefficients folded INTO the table entries), look each up once,
    and reduce every ``part_len``-pattern run. Returns
    (bm, bn, bk/g/part_len) — f32-exact integers ('take') or f32
    ('onehot')."""
    bm, bk = a.shape
    _, bn, bkg = planes.shape
    lut = _group_lut(a, group)
    amax = 1 << max(a_bits - 1, 0)
    acc = None
    for idx, clut, coef_sum in _paired_tile_luts(lut, planes, bits, group):
        s = _plane_lookup(clut, idx, lookup_impl)         # (bm, bn, bkg)
        if s.dtype == jnp.float32:                        # onehot path
            part = s.reshape(bm, bn, bkg // part_len, part_len).sum(-1)
        else:
            # int16 run sums stay safe while the worst-case magnitude
            # part_len * coef_sum * group * 2^(a_bits-1) fits 15 bits —
            # coef_sum reaches 12 for the w4 high pair, so the bound is
            # per-term, not global.
            acc_dtype = (jnp.int16
                         if part_len * coef_sum * group * amax < 2 ** 15
                         else jnp.int32)
            part = s.reshape(bm, bn, bkg // part_len, part_len) \
                    .sum(-1, dtype=acc_dtype).astype(jnp.int32)
        acc = part if acc is None else acc + part
    return acc


def _bs_kernel(a_ref, w_ref, o_ref, *, bits, group, a_bits, lookup_impl,
               k_axis):
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bkg = w_ref.shape[-1]
    acc = _plane_partials(a_ref[...], w_ref[...], bits=bits, group=group,
                          a_bits=a_bits, lookup_impl=lookup_impl,
                          part_len=bkg)                   # (bm, bn, 1)
    o_ref[...] += acc[..., 0].astype(jnp.float32)


def _bs_grouped_kernel(a_ref, w_ref, sc_ref, o_ref, *, bits, group, a_bits,
                       lookup_impl, group_size, k_axis):
    """Fused group-scale epilogue: each scale group's int16 partial is
    widened and scaled before accumulation (the weight planes carry no
    scale — this is the only float multiply in the loop)."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    gg = group_size // group                              # patterns / group
    acc = _plane_partials(a_ref[...], w_ref[...], bits=bits, group=group,
                          a_bits=a_bits, lookup_impl=lookup_impl,
                          part_len=gg)                    # (bm, bn, ng)
    sc = sc_ref[...]                                      # (bn, ng)
    o_ref[...] += (acc.astype(jnp.float32) * sc[None, :, :]).sum(-1)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "a_bits", "group", "group_size", "lookup_impl",
                     "bm", "bn", "bk", "interpret"),
)
def lut_gemm_bitsliced_pallas(
    a_codes: jax.Array,      # (M, K) int8 signed activation codes
    w_planes: jax.Array,     # (bits, N, K/g) uint8 plane patterns
    w_scales: jax.Array | None = None,   # (N, K/G) group-wise weight scales
    *,
    bits: int = 2,
    a_bits: int = 8,
    group: int = packing.BITPLANE_GROUP,
    group_size: int | None = None,
    lookup_impl: str = "take",
    bm: int = 8,
    bn: int = 256,
    bk: int = 512,           # in CODES; K-step per grid slot
    interpret: bool = False,
) -> jax.Array:
    """Blocked bit-sliced LUT GEMM. out[m,n] = sum_k w[n,k] * a_codes[m,k]
    with w the SIGNED weight code (plane-decomposed), f32-exact integers;
    group-wise ``w_scales`` fuse into the K loop when given. M <= GEMV_ROWS
    takes the GEMV tiling (full-M block, 2D grid)."""
    assert bits in (1, 2, 3, 4), bits
    M, K = a_codes.shape
    nplanes, N, Kg = w_planes.shape
    assert nplanes == bits and Kg * group == K, (a_codes.shape, w_planes.shape)
    grouped = w_scales is not None
    if grouped:
        assert group_size is not None and group_size % group == 0 \
            and K % group_size == 0, (K, group_size, group)

    gemv = M <= GEMV_ROWS
    bm = M if gemv else _fit(bm, M, SUBLANE)
    bn = _fit(bn, N, LANE)
    # a K step spans whole lane tiles of the pattern (and group-scale)
    # blocks, or the whole row
    k_align = math.lcm(LANE * group, LANE * group_size if grouped else 1)
    bk = _fit(bk, K, k_align)
    cap = 8 * 1024 * 1024
    # VMEM working set ~ the (bm, bn, bk/g) int32 gather tile + the LUT.
    while bm * bn * (bk // group) * 8 > cap:
        nbk, nbn = _shrink(bk, K, k_align), _shrink(bn, N, LANE)
        if nbk < bk:
            bk = nbk
        elif nbn < bn:
            bn = nbn
        else:
            break
    bkg = bk // group

    if gemv:
        grid = (N // bn, K // bk)
        k_axis = 1
        a_spec = pl.BlockSpec((bm, bk), lambda j, k: (0, k))
        w_spec = pl.BlockSpec((bits, bn, bkg), lambda j, k: (0, j, k))
        sc_spec = pl.BlockSpec((bn, bk // (group_size or 1)),
                               lambda j, k: (j, k))
        o_spec = pl.BlockSpec((bm, bn), lambda j, k: (0, j))
    else:
        grid = (M // bm, N // bn, K // bk)
        k_axis = 2
        a_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
        w_spec = pl.BlockSpec((bits, bn, bkg), lambda i, j, k: (0, j, k))
        sc_spec = pl.BlockSpec((bn, bk // (group_size or 1)),
                               lambda i, j, k: (j, k))
        o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))

    if grouped:
        kernel = functools.partial(
            _bs_grouped_kernel, bits=bits, group=group, a_bits=a_bits,
            lookup_impl=lookup_impl, group_size=group_size, k_axis=k_axis)
        in_specs = [a_spec, w_spec, sc_spec]
        args = [a_codes, w_planes, w_scales.astype(jnp.float32)]
    else:
        kernel = functools.partial(
            _bs_kernel, bits=bits, group=group, a_bits=a_bits,
            lookup_impl=lookup_impl, k_axis=k_axis)
        in_specs = [a_spec, w_spec]
        args = [a_codes, w_planes]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(*args)


# --------------------------------------------------------------------------- #
# Fused prologue: raw activations in, scaled f32 out
# --------------------------------------------------------------------------- #

def _row_scale(x: jax.Array, a_bits: int) -> jax.Array:
    """``quant.compute_scale_zero_point(axis=0)`` replicated in-kernel:
    per-row symmetric amax calibration in the INPUT dtype (a bf16 tile keeps
    a bf16 amax/scale, exactly like the two-step host-side call — the codes,
    and therefore the outputs, must match bitwise)."""
    bound = 1 << max(a_bits - 1, 0)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    return jnp.maximum(amax / bound, 1e-8)


def _quantize_tile(x: jax.Array, a_scale: jax.Array, a_bits: int) -> jax.Array:
    """``quant.quantize`` replicated in-kernel (same ops, same promotion)."""
    qmin, qmax = -(1 << (a_bits - 1)), (1 << (a_bits - 1)) - 1
    q = jnp.round(x / a_scale + 0.0)
    return jnp.clip(q, qmin, qmax).astype(jnp.int8)


def _bs_fused_kernel(*refs, bits, group, a_bits, group_size, lookup_impl,
                     has_asc):
    """Fused tile body: quantize the raw activation rows (dynamic amax or
    the prefetched static scale), run the paired-plane integer core over the
    FULL K row, and apply the complete scale epilogue — each output block is
    written once (no K grid axis; the dynamic amax is a whole-row
    reduction, which is why the fused kernel never tiles K)."""
    if has_asc:
        x_ref, w_ref, sc_ref, asc_ref, o_ref = refs
    else:
        x_ref, w_ref, sc_ref, o_ref = refs
    x = x_ref[...]
    a_scale = asc_ref[...] if has_asc else _row_scale(x, a_bits)
    aq = _quantize_tile(x, a_scale, a_bits)
    bkg = w_ref.shape[-1]
    if group_size is None:
        acc = _plane_partials(aq, w_ref[...], bits=bits, group=group,
                              a_bits=a_bits, lookup_impl=lookup_impl,
                              part_len=bkg)                  # (bm, bn, 1)
        y = acc[..., 0].astype(jnp.float32) * sc_ref[...][:, 0][None, :]
    else:
        gg = group_size // group
        acc = _plane_partials(aq, w_ref[...], bits=bits, group=group,
                              a_bits=a_bits, lookup_impl=lookup_impl,
                              part_len=gg)                   # (bm, bn, ng)
        y = (acc.astype(jnp.float32) * sc_ref[...][None, :, :]).sum(-1)
    o_ref[...] = y * a_scale.astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "a_bits", "group", "group_size", "lookup_impl",
                     "bm", "bn", "bk", "interpret"),
)
def lut_gemm_bs_fused_pallas(
    x: jax.Array,            # (M, K) raw bf16/f32 activations
    w_planes: jax.Array,     # (bits, N, K/g) uint8 plane patterns
    w_scales: jax.Array,     # (N,) per-channel | (N, K/G) group-wise
    a_sc: jax.Array | None = None,       # static (1,1) / explicit (M,1) scale
    *,
    bits: int = 2,
    a_bits: int = 8,
    group: int = packing.BITPLANE_GROUP,
    group_size: int | None = None,
    lookup_impl: str = "take",
    bm: int = 8,
    bn: int = 256,
    bk: int = 0,             # accepted for the (bm, bn, bk) block contract;
    interpret: bool = False,  # ignored — the fused kernel never tiles K
) -> jax.Array:
    """Fused-prologue bit-sliced LUT GEMM: activation quantization (dynamic
    per-row amax, or ``a_sc`` as-is), the paired-plane subset-sum core, and
    the full weight x activation scale epilogue in ONE kernel body.
    out = ((x / a_sc) . W^T_int) * w_scales * a_sc, bitwise identical to the
    two-step quantize -> lut_gemm_bitsliced -> epilogue route per-channel
    (group-wise: identical up to f32 rounding of the group-scale sum).

    Blocks hold the whole K row (the dynamic amax reduces over it), so the
    grid is (N/bn,) for decode shapes (M <= GEMV_ROWS) and (M/bm, N/bn)
    otherwise; ``bk`` is ignored."""
    del bk
    assert bits in (1, 2, 3, 4), bits
    M, K = x.shape
    nplanes, N, Kg = w_planes.shape
    assert nplanes == bits and Kg * group == K, (x.shape, w_planes.shape)
    grouped = group_size is not None
    if grouped:
        assert group_size % group == 0 and K % group_size == 0, \
            (K, group_size, group)

    gemv = M <= GEMV_ROWS
    bm = M if gemv else _fit(bm, M, SUBLANE)
    bn = _fit(bn, N, LANE)
    bkg = K // group
    cap = 8 * 1024 * 1024
    # VMEM working set ~ the (bm, bn, bkg) int32 gather tile (+ the paired
    # 2^(2g)-entry LUT, bm * bkg * 2^(2g) int16).
    while bm * bn * bkg * 8 > cap and _shrink(bn, N, LANE) < bn:
        bn = _shrink(bn, N, LANE)

    scv = w_scales.astype(jnp.float32)
    if not grouped:
        scv = scv.reshape(N, 1)
    ns = scv.shape[-1]
    has_asc = a_sc is not None

    if gemv:
        x_spec = pl.BlockSpec((bm, K), lambda j: (0, 0))
        w_spec = pl.BlockSpec((bits, bn, bkg), lambda j: (0, j, 0))
        sc_spec = pl.BlockSpec((bn, ns), lambda j: (j, 0))
        asc_spec = pl.BlockSpec((bm, 1), lambda j: (0, 0))
        o_spec = pl.BlockSpec((bm, bn), lambda j: (0, j))
        grid = (N // bn,)
    else:
        x_spec = pl.BlockSpec((bm, K), lambda i, j: (i, 0))
        w_spec = pl.BlockSpec((bits, bn, bkg), lambda i, j: (0, j, 0))
        sc_spec = pl.BlockSpec((bn, ns), lambda i, j: (j, 0))
        asc_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
        o_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
        grid = (M // bm, N // bn)

    in_specs = [x_spec, w_spec, sc_spec]
    args = [x, w_planes, scv]
    if has_asc:
        in_specs.append(asc_spec)
        args.append(jnp.broadcast_to(jnp.asarray(a_sc).reshape(-1, 1),
                                     (M, 1)))
    kernel = functools.partial(
        _bs_fused_kernel, bits=bits, group=group, a_bits=a_bits,
        group_size=group_size, lookup_impl=lookup_impl, has_asc=has_asc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(*args)
