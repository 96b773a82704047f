"""TPU-native beyond-paper kernel: packed sub-byte weights, in-VMEM codebook
dequantization (the paper's LUT, used as a codebook), MXU matmul.

This is the production serving path (DESIGN.md §2): on TPU, MACs are free on
the MXU and HBM bytes are the scarce resource, so the paper's
"lookup-instead-of-MAC" inverts into "lookup-instead-of-DEQUANT-MULTIPLY,
MACs stay on the MXU". What survives from the paper:

  * weights live in HBM packed at b bits (8x fewer bytes than bf16 at b=2),
  * the expansion goes through a table -> arbitrary non-uniform, signed or
    unsigned codebooks at identical cost (the paper's §5.3 flexibility),
  * per-channel scales fold into the epilogue (quant/dequant fusion).

Mosaic lowering. Byte j of a packed row holds codes k = j*f + i in slot i,
so unpacking slot i of a (bn, bk/f) tile yields the codes of every f-th K
column. Interleaving the slots back into (bn, bk) order is a lane shuffle
Mosaic cannot lower, so the activations are reordered instead: the wrapper
passes them slot-major, (f, M, K/f) with a_s[i, m, j] = a[m, j*f + i], and
the tile body contracts slot i of the weights against slot i of the
activations, f MXU dots per K step. The codebook lookup is a select chain
over the 2^bits levels, read as scalars from SMEM (a vector gather is not
lowerable). Every block is (8, 128)-legal (lut_gemm.matmul_blocks).

Memory layout per grid step (bm=128, bn=256, bk=512, bits=2, bf16
activations):
  a tile    (f, bm, bk/f) bf16  128 KiB  HBM->VMEM (widened to f32 per slot)
  w tile    (bn, bk/f) uint8     32 KiB  HBM->VMEM  (the 8x win vs bf16)
  w dequant (bn, bk/f) f32      128 KiB  per slot, VMEM only
  acc       (bm, bn) f32        128 KiB  VMEM, written once
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from .lut_gemm import (LANE, group_scale_tile, matmul_blocks, slot_major,
                       table_select)


def dequant_dot(a_slots, w: jax.Array, cb_ref, *, bits: int,
                scale: jax.Array | None = None) -> jax.Array:
    """Tile body shared with the expert kernel: sum over slots i of
    a_slots[i] (bm, bkp) @ dequant(slot i of w (bn, bkp)).T, f32 (bm, bn).
    ``scale`` (bn, bkp) multiplies the dequantized codes (group-wise). The
    activations arrive in their own dtype and are widened here, in VMEM."""
    sb, mask = packing.SLOT_BITS[bits], 2 ** bits - 1
    w = w.astype(jnp.int32)
    acc = None
    for i, a in enumerate(a_slots):
        wd = table_select((w >> (sb * i)) & mask, cb_ref, 2 ** bits)
        if scale is not None:
            wd = wd * scale
        part = jax.lax.dot_general(
            a.astype(jnp.float32), wd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc


def _dequant_matmul_kernel(a_ref, w_ref, cb_ref, sc_ref, o_ref, *, bits: int,
                           group_size: int | None):
    """Per-channel scales fold into the epilogue at the last K step;
    group-wise scales are K-position-dependent, so they scale the
    dequantized tile before the contraction."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    f = packing.PACK_FACTOR[bits]
    bkp = w_ref.shape[-1]
    scale = None
    if group_size is not None:
        scale = group_scale_tile(sc_ref[...], bkp, group_size // f)
    o_ref[...] += dequant_dot([a_ref[i] for i in range(f)], w_ref[...],
                              cb_ref, bits=bits, scale=scale)

    if group_size is None:
        @pl.when(k == pl.num_programs(2) - 1)
        def _epilogue():
            o_ref[...] = o_ref[...] * sc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bits", "group_size", "bm", "bn", "bk", "interpret")
)
def dequant_matmul_pallas(
    a: jax.Array,            # (M, K) bf16/f32
    w_packed: jax.Array,     # (N, K/f) uint8
    codebook: jax.Array,     # (2^bits,) f32 — dequant levels (non-uniform OK)
    scales: jax.Array,       # (N,) per-channel or (N, K/G) group-wise f32
    *,
    bits: int = 2,
    group_size: int | None = None,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out = (a @ dequant(w).T) * scales, f32. Weight-only quantization
    (w2a16/w4a16). ``group_size`` selects the group-wise scale formulation
    (scales (N, K/G))."""
    f = packing.PACK_FACTOR[bits]
    M, K = a.shape
    N, Kp = w_packed.shape
    assert Kp * f == K, (a.shape, w_packed.shape, bits)
    grouped = group_size is not None
    if grouped:
        assert group_size % f == 0 and K % group_size == 0, (K, group_size, f)
        assert scales.shape == (N, K // group_size), (scales.shape, N, K)
    bm, bn, bk = matmul_blocks(M, N, K, bits=bits, group_size=group_size,
                               bm=bm, bn=bn, bk=bk, scale_align=LANE)
    bkp = bk // f

    if grouped:
        scale_spec = pl.BlockSpec((bn, bk // group_size),
                                  lambda i, j, k: (j, k))
    else:
        scales = scales.reshape(1, N)
        scale_spec = pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
    return pl.pallas_call(
        functools.partial(_dequant_matmul_kernel, bits=bits,
                          group_size=group_size),
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((f, bm, bkp), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((bn, bkp), lambda i, j, k: (j, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            scale_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(slot_major(a, f), w_packed, codebook.astype(jnp.float32),
      scales.astype(jnp.float32))
