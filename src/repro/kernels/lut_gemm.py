"""Paper-faithful LUT GEMM as a Pallas TPU kernel (paper §3.2 LUT-16, §4.2).

Structure mirrors Algorithm 1 of the paper, re-tiled for the TPU memory
hierarchy:

  HBM:   packed sub-byte weights (uint8 carriers, f codes per byte) and the
         uint8 activation codes, reordered slot-major by the wrapper
  SMEM:  the whole product LUT (16/64/256/4096 entries), read as scalars
  VMEM:  one (f, bm, bk/f) activation-code tile, one (bn, bk/f) packed
         weight tile, one (bm, bn) f32 accumulator tile
  VPU:   unpack (shift/and — the paper's masking step) and table lookup
  MXU:   accumulation of the looked-up products

No multiply touches the operand values — multiplication happens *offline*
when the LUT is built, which is the paper's whole point.

Mosaic lowers neither a vector gather from the table (``jnp.take``) nor the
(bm, bn, bk) index tile of a direct port, so the lookup is regrouped by
weight level (``lut_dot``): row v of the table is read by the activation
codes (a select chain over SMEM scalars, standing in for AVX2 pshufb), and
the MXU sums it against the 0/1 mask of the weights whose code is v.

Cost per K step: f weight slots x 2^bits levels = f * 2^bits MXU dots of
(3 bm, bk/f) x (bk/f, bn) in bf16 (the table row split into three exact
parts, ``bf16_split``), and 2^(bits+a_bits) selects per slot. Group-wise
scales multiply the dots by the bk/G groups of the step: each group's sum
is its own dot against the level mask restricted to that group's bytes,
so the unscaled sums stay exact.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing


SUBLANE, LANE = 8, 128   # Mosaic's (8, 128) tiling of a block's last two dims


def table_select(codes: jax.Array, table_ref, n: int, base: int = 0):
    """table[base + codes] for codes in [0, n), as a select chain over SMEM
    scalars: Mosaic lowers no vector gather from a 1-D table."""
    out = jnp.full(codes.shape, table_ref[base], jnp.float32)
    for v in range(1, n):
        out = jnp.where(codes == v, table_ref[base + v], out)
    return out


def group_ids(shape: tuple[int, int], bytes_per_group: int) -> jax.Array:
    """Scale-group index of each packed weight byte of a (bn, bkp) tile
    (every slot of a byte shares its group: G is a pack-factor multiple)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // bytes_per_group


def group_scale_tile(sc: jax.Array, bkp: int, bytes_per_group: int):
    """(bn, ng) group scales -> (bn, bkp) per packed weight byte."""
    gid = group_ids((sc.shape[0], bkp), bytes_per_group)
    out = jnp.zeros((sc.shape[0], bkp), jnp.float32)
    for g in range(sc.shape[1]):
        out = jnp.where(gid == g, sc[:, g:g + 1], out)
    return out


def slot_major(a: jax.Array, f: int) -> jax.Array:
    """(..., M, K) -> (..., f, M, K/f) with out[..., i, m, j] =
    a[..., m, j*f + i]: the activation order that pairs with slot i of the
    natural packed weight layout (byte j holds codes j*f .. j*f+f-1)."""
    *lead, M, K = a.shape
    a = a.reshape(*lead, M, K // f, f)
    return jnp.moveaxis(a, -1, -3)


def bf16_split(x: jax.Array) -> jax.Array:
    """(R, C) f32 -> (3R, C) bf16 rows [hi; mid; lo] with hi + mid + lo == x
    exactly. A bf16 operand carries 8 significant bits and three carry all
    24 of an f32, so a bf16 MXU dot of the parts against a 0/1 mask sums the
    f32 values themselves (an f32 dot at default precision would round them
    to bf16 first: 7 * 127 of a w4a8 table is not a bf16)."""
    parts = []
    for _ in range(3):
        p = x.astype(jnp.bfloat16).astype(jnp.float32)
        parts.append(p)
        x = x - p
    return jnp.concatenate(parts, axis=0).astype(jnp.bfloat16)


def lut_dot(a_slots, w: jax.Array, lut_ref, *, bits: int, a_bits: int,
            n_groups: int = 1) -> list[jax.Array]:
    """One K step of the product-LUT GEMM: the f32 (bm, bn) sums
    sum_k LUT[(w[n,k] << a_bits) | a[m,k]] over each of ``n_groups`` equal
    runs of the tile's weight bytes (its scale groups), in order.

    ``a_slots[i]`` (bm, bkp) holds the activation codes that pair with
    slot i of the packed weight bytes ``w`` (bn, bkp). For each weight level
    v the activation codes read row v of the table (``table_select``), and
    the MXU sums that row against the 0/1 mask of the weights equal to v:
    every product is a table entry, as in the paper, and Mosaic needs
    neither a gather nor a 3-D index tile. The row enters the MXU as three
    stacked bf16 parts (``bf16_split``), so the sums hold the exact
    unscaled table entries: with integer levels they are exact in any
    order."""
    sb, mask = packing.SLOT_BITS[bits], 2 ** bits - 1
    w = w.astype(jnp.int32)
    a_slots = [a.astype(jnp.int32) for a in a_slots]
    bm = a_slots[0].shape[0]
    gid = group_ids(w.shape, w.shape[-1] // n_groups) if n_groups > 1 else None
    acc = [None] * n_groups
    for i, a in enumerate(a_slots):
        wi = (w >> (sb * i)) & mask
        for v in range(2 ** bits):
            rows = bf16_split(table_select(a, lut_ref, 2 ** a_bits,
                                           v << a_bits))
            hit = wi == v
            for g in range(n_groups):
                sel = hit if n_groups == 1 else hit & (gid == g)
                part = jax.lax.dot_general(
                    rows, sel.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                part = part[:bm] + part[bm:2 * bm] + part[2 * bm:]
                acc[g] = part if acc[g] is None else acc[g] + part
    return acc


def scaled_sum(parts: list[jax.Array], sc: jax.Array) -> jax.Array:
    """sum_g parts[g] * sc[g] for group scales ``sc`` (ng, bn), in group
    order, so every output element sums the same way whatever the tile."""
    out = parts[0] * sc[0:1, :]
    for g in range(1, len(parts)):
        out = out + parts[g] * sc[g:g + 1, :]
    return out


def _lut_gemm_kernel(a_ref, w_ref, lut_ref, *refs, bits: int, a_bits: int,
                     group_size: int | None):
    """Group-wise scales (when given, transposed (K/G, N)) weight each
    group's exact partial sum inside the K loop: the LUT holds unscaled
    level products."""
    sc_ref, o_ref = refs if group_size is not None else (None, *refs)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    f = packing.PACK_FACTOR[bits]
    a_slots = [a_ref[i] for i in range(f)]
    if group_size is None:
        o_ref[...] += lut_dot(a_slots, w_ref[...], lut_ref, bits=bits,
                              a_bits=a_bits)[0]
    else:
        parts = lut_dot(a_slots, w_ref[...], lut_ref, bits=bits,
                        a_bits=a_bits, n_groups=sc_ref.shape[0])
        o_ref[...] += scaled_sum(parts, sc_ref[...])


def _fit(target: int, n: int, align: int) -> int:
    """Block length for an array dim of length ``n``: the largest multiple
    of ``align`` that divides ``n`` and is <= ``target``, else ``n`` itself.
    Mosaic accepts a block whose last two dims are multiples of
    (SUBLANE, LANE) or equal to the full array dims, so every result is a
    legal block; ``align`` is SUBLANE or LANE for the dim's role (times the
    codes per lane element when the dim is counted in codes)."""
    b = (min(target, n) // align) * align
    while b >= align:
        if n % b == 0:
            return b
        b -= align
    return n


def _shrink(b: int, n: int, align: int) -> int:
    """Next smaller legal block below ``b`` (``b`` itself if none)."""
    s = _fit(b // 2, n, align)
    return s if s < b else b


# Working-set budget per grid step (double-buffered blocks + temporaries),
# under the 16 MiB scoped-VMEM default of v5e.
VMEM_BUDGET = 12 * 1024 * 1024


def matmul_blocks(M: int, N: int, K: int, *, bits: int,
                  group_size: int | None, bm: int, bn: int, bk: int,
                  scale_align: int = SUBLANE, a_bits: int | None = None):
    """Legal (bm, bn, bk) for the packed-weight matmul kernels (this one and
    the dequant ones), shrunk until the working set fits VMEM_BUDGET. bk is
    in codes: a K step spans whole lane tiles of the packed weights, or the
    whole row. With group-wise scales it also spans whole tiles of the
    scale block's K/G dim: ``scale_align`` is SUBLANE when that dim is the
    block's second-to-last (scales stored (K/G, N)), LANE when it is the
    last ((N, K/G)). ``a_bits`` sizes the temporaries of the LUT body
    (``lut_dot``) in place of the dequant body's."""
    f = packing.PACK_FACTOR[bits]
    k_align = math.lcm(LANE * f, scale_align * group_size if group_size else 1)
    bm, bn, bk = _fit(bm, M, SUBLANE), _fit(bn, N, LANE), _fit(bk, K, k_align)

    def vmem(bm, bn, bk):
        bkp = bk // f
        blocks = 2 * (f * bm * bkp * 4 + bn * bkp + bn * 4 * max(
            1, bk // (group_size or bk))) + 2 * bm * bn * 4
        if a_bits is None:                    # dequant_dot
            return blocks + (f + 3) * bn * bkp * 4
        # lut_dot: the weight masks of every (slot, level), the
        # activation-code compares, which Mosaic shares across levels, and
        # a table row's three f32 parts and their bf16 stack
        return blocks + 4 * f * (2 ** bits * max(1, a_bits // 2) * bn
                                 + 2 ** a_bits * bm) * bkp + 18 * bm * bkp

    while vmem(bm, bn, bk) > VMEM_BUDGET:
        nbk, nbn, nbm = (_shrink(bk, K, k_align), _shrink(bn, N, LANE),
                         _shrink(bm, M, SUBLANE))
        if nbk < bk:
            bk = nbk
        elif nbn < bn:
            bn = nbn
        elif nbm < bm:
            bm = nbm
        else:
            raise ValueError(
                f"no (8,128)-legal block of M={M} N={N} K={K} fits "
                f"{VMEM_BUDGET} bytes of VMEM")
    return bm, bn, bk


@functools.partial(
    jax.jit,
    static_argnames=("bits", "a_bits", "group_size", "bm", "bn", "bk",
                     "interpret"),
)
def lut_gemm_pallas(
    a_idx: jax.Array,        # (M, K) uint8 activation codes
    w_packed: jax.Array,     # (N, K/fw) uint8
    lut_table: jax.Array,    # (2^(bits + a_bits),) f32/int32
    w_scales: jax.Array | None = None,   # (N, K/G) group-wise weight scales
    *,
    bits: int = 2,
    a_bits: int | None = None,   # activation code width (default: == bits)
    group_size: int | None = None,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,           # in CODES (not bytes)
    interpret: bool = False,
) -> jax.Array:
    """Blocked LUT GEMM. out[m,n] = sum_k LUT[(w[n,k]<<a_bits) | a[m,k]], f32.

    ``bits``/``a_bits`` are the weight/activation code widths. The
    activation codes arrive unpacked, one per byte, and are reordered
    slot-major by the weight's pack factor here (``slot_major``, which XLA
    fuses into the producer of the codes), so the kernel sees one layout
    and never has to interleave unpacked slots.

    With ``w_scales``/``group_size`` each group's products are scaled inside
    the K loop: out[m,n] = sum_g s[n,g] * sum_{k in g} LUT[...].

    The tile body costs 2^bits MXU dots (times bk/G when grouped) and
    2^(bits+a_bits) selects per weight slot and K step (``lut_dot``):
    cheap at w2a2, heavy for wide tables.
    """
    if a_bits is None:
        a_bits = bits
    fw = packing.PACK_FACTOR[bits]
    M, K = a_idx.shape
    N, Kpw = w_packed.shape
    assert Kpw * fw == K, (a_idx.shape, w_packed.shape, bits)
    grouped = w_scales is not None
    if grouped:
        assert group_size is not None and group_size % fw == 0 \
            and K % group_size == 0, (K, group_size, fw)
    else:
        group_size = None
    bm, bn, bk = matmul_blocks(M, N, K, bits=bits, group_size=group_size,
                               bm=bm, bn=bn, bk=bk, a_bits=a_bits)
    bkp = bk // fw

    in_specs = [
        pl.BlockSpec((fw, bm, bkp), lambda i, j, k: (0, i, k)),
        pl.BlockSpec((bn, bkp), lambda i, j, k: (j, k)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    args = [slot_major(a_idx.astype(jnp.uint8), fw), w_packed,
            lut_table.astype(jnp.float32)]
    if grouped:
        in_specs.append(
            pl.BlockSpec((bk // group_size, bn), lambda i, j, k: (k, j)))
        args.append(w_scales.astype(jnp.float32).T)
    return pl.pallas_call(
        functools.partial(_lut_gemm_kernel, bits=bits, a_bits=a_bits,
                          group_size=group_size),
        grid=(M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
