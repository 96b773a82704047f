"""Grouped (per-expert) packed-weight matmul — the MoE serving hot-spot.

Expert weights are where the paper's 2-bit packing buys the most (llama4:
386B of 397B params live in expert matrices), and expert GEMMs are the
batched/grouped form of `lut_dequant_matmul`: for every expert e,

    out[e] = (x[e] @ dequant(w[e]).T) * scales[e]

with x[e] the (capacity-padded) tokens dispatched to e. The kernel walks a
(E, M-tiles, N-tiles, K-tiles) grid; each step unpacks one expert's packed
sub-byte tile in VMEM, codebook-dequantizes (uniform or k-means table — the
paper's flexibility), and contracts on the MXU, with the dense kernel's
slot-major tile body (lut_dequant_matmul.dequant_dot).

Memory layout per grid step (be=1, bm=128, bn=128, bk=512, bits=2):
  x tile     (f, bm, bk/f) bf16     128 KiB  HBM->VMEM
  w tile     (bn, bk/4) uint8        16 KiB  HBM->VMEM  (the 8x win)
  w dequant  (bn, bk/4) f32          64 KiB  per slot, VMEM only
  acc        (bm, bn) f32            64 KiB  VMEM
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from .lut_dequant_matmul import dequant_dot
from .lut_gemm import (LANE, group_scale_tile, lut_dot, matmul_blocks,
                       scaled_sum, slot_major)


def _expert_kernel(x_ref, w_ref, cb_ref, sc_ref, o_ref, *, bits: int,
                   group_size: int | None):
    """One expert's tile: the dense kernel's body (dequant_dot) on the
    expert's slot-major activations; per-channel scales in the epilogue,
    group-wise scales on the dequantized tile."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    f = packing.PACK_FACTOR[bits]
    bkp = w_ref.shape[-1]
    scale = None
    if group_size is not None:
        scale = group_scale_tile(sc_ref[0], bkp, group_size // f)
    o_ref[0] += dequant_dot([x_ref[0, i] for i in range(f)], w_ref[0],
                            cb_ref, bits=bits, scale=scale)

    if group_size is None:
        @pl.when(k == pl.num_programs(3) - 1)
        def _epilogue():
            o_ref[0] = o_ref[0] * sc_ref[0]


@functools.partial(
    jax.jit, static_argnames=("bits", "group_size", "bm", "bn", "bk",
                              "interpret"))
def expert_dequant_matmul_pallas(
    x: jax.Array,            # (E, M, K) tokens per expert (capacity-padded)
    w_packed: jax.Array,     # (E, N, K/f) uint8
    codebook: jax.Array,     # (2^bits,) f32
    scales: jax.Array,       # (E, N) per-channel or (E, N, K/G) group-wise
    *,
    bits: int = 2,
    group_size: int | None = None,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out[e] = (x[e] @ dequant(w[e]).T) * scales[e], f32, (E, M, N)."""
    f = packing.PACK_FACTOR[bits]
    E, M, K = x.shape
    E2, N, Kp = w_packed.shape
    assert E == E2 and Kp * f == K, (x.shape, w_packed.shape, bits)
    grouped = group_size is not None
    if grouped:
        assert group_size % f == 0 and K % group_size == 0, (K, group_size, f)
        assert scales.shape == (E, N, K // group_size), (scales.shape,)
    bm, bn, bk = matmul_blocks(M, N, K, bits=bits, group_size=group_size,
                               bm=bm, bn=bn, bk=bk, scale_align=LANE)
    bkp = bk // f

    if grouped:
        scale_spec = pl.BlockSpec((1, bn, bk // group_size),
                                  lambda e, i, j, k: (e, j, k))
    else:
        scales = scales.reshape(E, 1, N)
        scale_spec = pl.BlockSpec((1, 1, bn), lambda e, i, j, k: (e, 0, j))
    return pl.pallas_call(
        functools.partial(_expert_kernel, bits=bits, group_size=group_size),
        grid=(E, M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((1, f, bm, bkp), lambda e, i, j, k: (e, 0, i, k)),
            pl.BlockSpec((1, bn, bkp), lambda e, i, j, k: (e, j, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            scale_spec,
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(slot_major(x, f), w_packed, codebook.astype(jnp.float32),
      scales.astype(jnp.float32))


# --------------------------------------------------------------------------- #
# Activation-quantized expert LUT GEMM (w{b}a{b} MoE path)
# --------------------------------------------------------------------------- #

def _expert_lut_kernel(a_ref, w_ref, lut_ref, *refs, bits: int,
                       group_size: int | None):
    """One expert's tile: the dense LUT kernel's body (lut_gemm.lut_dot);
    group scales arrive transposed, (E, K/G, N)."""
    sc_ref, o_ref = refs if group_size is not None else (None, *refs)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    f = packing.PACK_FACTOR[bits]
    a_slots = [a_ref[0, i] for i in range(f)]
    if group_size is None:
        o_ref[0] += lut_dot(a_slots, w_ref[0], lut_ref, bits=bits,
                            a_bits=bits)[0]
    else:
        parts = lut_dot(a_slots, w_ref[0], lut_ref, bits=bits, a_bits=bits,
                        n_groups=sc_ref.shape[1])
        o_ref[0] += scaled_sum(parts, sc_ref[0])


@functools.partial(
    jax.jit, static_argnames=("bits", "group_size", "bm", "bn", "bk",
                              "interpret"))
def expert_lut_gemm_pallas(
    a_idx: jax.Array,        # (E, M, K) uint8 per-expert activation codes
    w_packed: jax.Array,     # (E, N, K/f) uint8
    lut_table: jax.Array,    # (2^(2*bits),) product LUT (w_bits == a_bits)
    w_scales: jax.Array | None = None,   # (E, N, K/G) group-wise
    *,
    bits: int = 2,
    group_size: int | None = None,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-expert LUT GEMM: out[e,m,n] = sum_k LUT[(w[e,n,k]<<b) | a[e,m,k]].

    The batched/grouped form of ``lut_gemm_pallas`` — the grid walks
    (E, M-tiles, N-tiles, K-tiles) like ``expert_dequant_matmul_pallas``
    and the tile body is the dense kernel's multiply-free lookup. Like
    ``lut_gemm``, per-channel weight scales stay in the caller's epilogue;
    group-wise scales fuse into the K loop.
    """
    f = packing.PACK_FACTOR[bits]
    E, M, K = a_idx.shape
    E2, N, Kp = w_packed.shape
    assert E == E2 and Kp * f == K, (a_idx.shape, w_packed.shape)
    grouped = w_scales is not None
    if grouped:
        assert group_size is not None and group_size % f == 0 \
            and K % group_size == 0, (K, group_size, f)
        assert w_scales.shape == (E, N, K // group_size), (w_scales.shape,)
    else:
        group_size = None
    bm, bn, bk = matmul_blocks(M, N, K, bits=bits, group_size=group_size,
                               bm=bm, bn=bn, bk=bk, a_bits=bits)
    bkp = bk // f

    in_specs = [
        pl.BlockSpec((1, f, bm, bkp), lambda e, i, j, k: (e, 0, i, k)),
        pl.BlockSpec((1, bn, bkp), lambda e, i, j, k: (e, j, k)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    args = [slot_major(a_idx.astype(jnp.uint8), f), w_packed,
            lut_table.astype(jnp.float32)]
    if grouped:
        in_specs.append(pl.BlockSpec((1, bk // group_size, bn),
                                     lambda e, i, j, k: (e, k, j)))
        args.append(jnp.swapaxes(w_scales.astype(jnp.float32), 1, 2))
    return pl.pallas_call(
        functools.partial(_expert_lut_kernel, bits=bits,
                          group_size=group_size),
        grid=(E, M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
