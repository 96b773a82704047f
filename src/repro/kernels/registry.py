"""KernelOp registry — the single dispatch surface for DeepGEMM kernels.

PR 4/5 grew five hand-written wrappers in kernels/ops.py, each re-implementing
the same three concerns: backend resolution, tensor-parallel shard_map
wrapping, and trace-time dispatch counting. This module replaces them with a
declarative registry: an op states ONCE

  ref         the pure-jnp oracle (XLA-optimized; also what the 512-way SPMD
              dry-run traces so GSPMD sees shardable HLO)
  pallas      the Pallas lowering (kwargs: ``interpret`` plus optional
              ``bm``/``bn``/``bk`` tile overrides)
  tp_rule     how to shard it: (role, ax, n_shards, arrays, static) ->
              (in_specs, out_spec, reduce) or None to fall back unsharded —
              'col' shards the output dim with no collective, 'row' shards
              the contraction dim with one psum (reduce=True)
  tile_space  candidate (bm, bn, bk) blocks for the offline autotuner

and every caller goes through ``dispatch(name, *arrays, ...)``. Optional
operands (e.g. group-wise scales) are passed positionally as ``None``; the
dispatcher filters them out of the shard_map arity and reinserts the slots
before calling the impl.

Backends (same contract as the old wrappers):
  'ref' | 'pallas_interpret' | 'pallas' | 'auto' (pallas on TPU else
  interpret). Every dispatch records a trace-time
  ``kernel_dispatch_total{op,backend,m_bucket,bits}`` counter into the
  repro.obs metrics registry stack, so tests and the CI serving gate can
  assert a planned model actually reached its kernel route — read it with
  ``obs.metrics.scoped()`` (isolated) or
  ``obs.metrics.global_registry().dispatch_counts()`` (process view). The
  PR 6/7 ``DISPATCH_COUNTS``/``dispatch_counts``/``reset_dispatch_counts``
  deprecation shims are REMOVED; ``kernels.ops`` raises with a pointer at
  the first stale access.

QuantPlan's ``kernel`` route field resolves to a registry name — registering
a new KernelOp is all it takes to give a plan a new route (the bit-sliced
'lut_gemm_bitsliced' op and its fused-prologue sibling 'lut_gemm_bs_fused'
enter exactly this way).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
from jax.sharding import PartitionSpec as P

from repro.core.lut import ProductLUT
from repro.dist import sharding as dsh
from repro.obs import metrics as obs_metrics
from . import ref as _ref
from .lut_gemm import lut_gemm_pallas
from .lut_gemm_bitsliced import (lut_gemm_bitsliced_pallas,
                                 lut_gemm_bs_fused_pallas)
from .lut_dequant_matmul import dequant_matmul_pallas
from .expert_dequant_matmul import (expert_dequant_matmul_pallas,
                                    expert_lut_gemm_pallas)
from .kv_cache_attention import kv_cache_attention_pallas
from .paged_attention import (paged_attention_pallas,
                              paged_attention_splitkv_pallas)


def _count(op: str, backend: str, m=None, bits=None) -> None:
    obs_metrics.record_kernel_dispatch(op, backend, m=m, bits=bits)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    if backend != "auto":
        return backend
    return "pallas" if _on_tpu() else "pallas_interpret"


def _tp_active(tp: str | None):
    """(mesh, axis, n_shards) when a TP role should be honoured, else None."""
    if tp not in ("col", "row"):
        return None
    ctx = dsh.active_tp()
    if ctx is None:
        return None
    mesh, ax = ctx
    if ax not in mesh.shape or mesh.shape[ax] <= 1:
        return None
    return mesh, ax, mesh.shape[ax]


@dataclasses.dataclass(frozen=True)
class KernelOp:
    """One kernel's complete dispatch contract (see module docstring)."""
    name: str
    ref: Callable[..., jax.Array]
    pallas: Callable[..., jax.Array] | None = None
    tp_rule: Callable[..., tuple | None] | None = None
    tile_space: Callable[..., list[tuple[int, int, int]]] | None = None
    doc: str = ""


_REGISTRY: dict[str, KernelOp] = {}


def register(op: KernelOp) -> KernelOp:
    assert op.name not in _REGISTRY, f"duplicate kernel op {op.name!r}"
    _REGISTRY[op.name] = op
    return op


def get(name: str) -> KernelOp:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel op {name!r}; registered: {op_names()}") from None


def op_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def dispatch(
    name: str,
    *arrays: jax.Array | None,
    backend: str = "auto",
    block: tuple[int, int, int] | None = None,
    tp: str | None = None,
    **static: Any,
) -> jax.Array:
    """Run a registered kernel op: resolve the backend, count the dispatch,
    and honour the op's TP rule when a dist.sharding.use_tp context is
    active. ``None`` operands mark optional slots (filtered from shard_map).
    ``block`` overrides the Pallas (bm, bn, bk) tile — ignored by 'ref'."""
    op = get(name)
    b = resolve_backend(backend)
    m = next((int(x.shape[0]) for x in arrays
              if x is not None and getattr(x, "ndim", 0) >= 2), None)
    _count(op.name, b, m=m, bits=static.get("w_bits", static.get("bits")))
    blk = {}
    if block is not None and b != "ref" and op.pallas is not None:
        blk = dict(bm=block[0], bn=block[1], bk=block[2])
    none_mask = tuple(x is None for x in arrays)
    present = tuple(x for x in arrays if x is not None)

    def compute(*xs):
        it = iter(xs)
        full = tuple(None if m else next(it) for m in none_mask)
        if b == "ref" or op.pallas is None:
            return op.ref(*full, **static)
        return op.pallas(*full, interpret=(b == "pallas_interpret"),
                         **blk, **static)

    ctx = _tp_active(tp)
    if ctx is not None and op.tp_rule is not None:
        mesh, ax, n = ctx
        rule = op.tp_rule(tp, ax, n, arrays, static)
        if rule is not None:
            in_specs, out_spec, reduce_out = rule
            in_specs = tuple(s for s, m in zip(in_specs, none_mask) if not m)
            fn = compute
            if reduce_out:
                fn = lambda *xs: jax.lax.psum(compute(*xs), ax)  # noqa: E731
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_spec,
                                 check_vma=False)(*present)
    return compute(*present)


# --------------------------------------------------------------------------- #
# TP rules (ported verbatim from the PR 4/5 wrappers; specs cover the FULL
# positional arity — None-slot specs are dropped by the dispatcher)
# --------------------------------------------------------------------------- #

def _lut_gemm_tp(role, ax, n, arrays, static):
    a_idx, w_packed, _table, sc = arrays
    N, Kp = w_packed.shape
    ok = (N % n == 0 if role == "col"
          else Kp % n == 0 and a_idx.shape[-1] % n == 0)
    if static.get("group_size") is not None and sc is not None:
        ok = ok and (sc.shape[-1] % n == 0 or role == "col")
    if not ok:
        return None
    if role == "col":
        return (P(), P(ax), P(), P(ax)), P(None, ax), False
    return (P(None, ax), P(None, ax), P(), P(None, ax)), P(), True


def _dequant_matmul_tp(role, ax, n, arrays, static):
    a, w_packed, _cb, scales = arrays
    N, Kp = w_packed.shape
    grouped = static.get("group_size") is not None
    if role == "col":
        if N % n != 0:
            return None
        return ((P(), P(ax), P(), P(ax, None) if grouped else P(ax)),
                P(None, ax), False)
    ok = Kp % n == 0 and a.shape[-1] % n == 0 \
        and (not grouped or scales.shape[-1] % n == 0)
    if not ok:
        return None
    # per-channel scales are applied per output column inside the kernel
    # epilogue — that commutes with the psum over partials
    return ((P(None, ax), P(None, ax), P(), P(None, ax) if grouped else P()),
            P(), True)


def _expert_dequant_matmul_tp(role, ax, n, arrays, static):
    x, w_packed, _cb, scales = arrays
    _, N, Kp = w_packed.shape
    grouped = static.get("group_size") is not None
    if role == "col":
        if N % n != 0:
            return None
        return ((P(), P(None, ax), P(),
                 P(None, ax, None) if grouped else P(None, ax)),
                P(None, None, ax), False)
    ok = Kp % n == 0 and x.shape[-1] % n == 0 \
        and (not grouped or scales.shape[-1] % n == 0)
    if not ok:
        return None
    return ((P(None, None, ax), P(None, None, ax), P(),
             P(None, None, ax) if grouped else P()), P(), True)


def _expert_lut_gemm_tp(role, ax, n, arrays, static):
    a_idx, w_packed, _table, sc = arrays
    _, N, Kp = w_packed.shape
    ok = (N % n == 0 if role == "col"
          else Kp % n == 0 and a_idx.shape[-1] % n == 0
          and (sc is None or sc.shape[-1] % n == 0))
    if not ok:
        return None
    if role == "col":
        return ((P(), P(None, ax), P(), P(None, ax, None)),
                P(None, None, ax), False)
    return ((P(None, None, ax), P(None, None, ax), P(), P(None, None, ax)),
            P(), True)


def _bitsliced_tp(role, ax, n, arrays, static):
    a_codes, w_planes, sc = arrays
    _bits, N, Kg = w_planes.shape
    if role == "col":
        if N % n != 0:
            return None
        return ((P(), P(None, ax, None),
                 P(ax, None) if sc is not None else P()),
                P(None, ax), False)
    # row: K split at pattern granularity keeps plane bytes whole; scale
    # groups stay shard-local when the scale axis divides too.
    ok = Kg % n == 0 and a_codes.shape[-1] % n == 0 \
        and (sc is None or sc.shape[-1] % n == 0)
    if not ok:
        return None
    return ((P(None, ax), P(None, None, ax),
             P(None, ax) if sc is not None else P()), P(), True)


def _bs_fused_tp(role, ax, n, arrays, static):
    """Fused prologue shards column-wise only: activations stay replicated
    (each shard re-quantizes its own copy — cheap, and the row amax needs
    the full K row, so a K split would change the scales). 'row' returns
    None and dense_serve falls back to the two-step route."""
    if role != "col":
        return None
    _x, w_planes, sc, _a_sc = arrays
    _bits, N, _Kg = w_planes.shape
    if N % n != 0:
        return None
    grouped = static.get("group_size") is not None
    return ((P(), P(None, ax, None),
             P(ax, None) if grouped else P(ax), P()),
            P(None, ax), False)


# --------------------------------------------------------------------------- #
# Tile spaces — candidate Pallas blocks for the offline autotuner
# --------------------------------------------------------------------------- #

def _matmul_tile_space(m, k, n, static):
    if m <= 4:  # decode / GEMV shapes: trade M tiling for wider N and deep K
        return [(m, 128, 512), (m, 256, 512), (m, 256, 1024),
                (m, 512, 512), (m, 512, 256)]
    return [(128, 128, 512), (128, 256, 512), (64, 256, 512),
            (64, 128, 1024), (32, 256, 256)]


def _bs_fused_tile_space(m, k, n, static):
    # the fused prologue never tiles K (the dynamic amax reduces over the
    # whole row), so only (bm, bn) vary; bk=0 keeps the block contract
    if m <= 4:
        return [(m, 128, 0), (m, 256, 0), (m, 512, 0)]
    return [(8, 256, 0), (8, 128, 0), (16, 256, 0)]


# --------------------------------------------------------------------------- #
# Impl adapters: registry positional arity -> each kernel's own signature
# --------------------------------------------------------------------------- #

def _lut_gemm_ref(a_idx, wp, table, sc, *, w_bits, a_bits, group_size=None):
    return _ref.ref_lut_gemm(a_idx, wp, ProductLUT(table, w_bits, a_bits),
                             w_scales=sc, group_size=group_size)


def _lut_gemm_pl(a_idx, wp, table, sc, *, w_bits, a_bits, group_size=None,
                 interpret=False, **blk):
    return lut_gemm_pallas(a_idx, wp, table, sc, bits=w_bits, a_bits=a_bits,
                           group_size=group_size, interpret=interpret, **blk)


def _dequant_matmul_ref(a, wp, cb, sc, *, bits, group_size=None):
    return _ref.ref_dequant_matmul(a, wp, cb, sc, bits,
                                   group_size=group_size)


def _dequant_matmul_pl(a, wp, cb, sc, *, bits, group_size=None,
                       interpret=False, **blk):
    return dequant_matmul_pallas(a, wp, cb, sc, bits=bits,
                                 group_size=group_size, interpret=interpret,
                                 **blk)


def _bitsliced_ref(a_codes, planes, sc, *, w_bits, a_bits=8, group=None,
                   group_size=None, lookup_impl="take"):
    del a_bits, lookup_impl
    from repro.core import packing
    return _ref.ref_lut_gemm_bitsliced(
        a_codes, planes, sc, bits=w_bits,
        group=group or packing.BITPLANE_GROUP, group_size=group_size)


def _bitsliced_pl(a_codes, planes, sc, *, w_bits, a_bits=8, group=None,
                  group_size=None, lookup_impl="take", interpret=False,
                  **blk):
    from repro.core import packing
    return lut_gemm_bitsliced_pallas(
        a_codes, planes, sc, bits=w_bits, a_bits=a_bits,
        group=group or packing.BITPLANE_GROUP, group_size=group_size,
        lookup_impl=lookup_impl, interpret=interpret, **blk)


def _bs_fused_ref(x, planes, sc, a_sc, *, w_bits, a_bits=8, group=None,
                  group_size=None, lookup_impl="take"):
    del lookup_impl
    from repro.core import packing
    return _ref.ref_lut_gemm_bs_fused(
        x, planes, sc, a_sc, w_bits=w_bits, a_bits=a_bits,
        group=group or packing.BITPLANE_GROUP, group_size=group_size)


def _bs_fused_pl(x, planes, sc, a_sc, *, w_bits, a_bits=8, group=None,
                 group_size=None, lookup_impl="take", interpret=False,
                 **blk):
    from repro.core import packing
    return lut_gemm_bs_fused_pallas(
        x, planes, sc, a_sc, bits=w_bits, a_bits=a_bits,
        group=group or packing.BITPLANE_GROUP, group_size=group_size,
        lookup_impl=lookup_impl, interpret=interpret, **blk)


def _expert_dequant_ref(x, wp, cb, sc, *, bits, group_size=None):
    return _ref.ref_expert_dequant_matmul(x, wp, cb, sc, bits,
                                          group_size=group_size)


def _expert_dequant_pl(x, wp, cb, sc, *, bits, group_size=None,
                       interpret=False, **blk):
    return expert_dequant_matmul_pallas(x, wp, cb, sc, bits=bits,
                                        group_size=group_size,
                                        interpret=interpret, **blk)


def _expert_lut_ref(a_idx, wp, table, sc, *, w_bits, a_bits,
                    group_size=None):
    return _ref.ref_expert_lut_gemm(a_idx, wp,
                                    ProductLUT(table, w_bits, a_bits),
                                    w_scales=sc, group_size=group_size)


def _expert_lut_pl(a_idx, wp, table, sc, *, w_bits, a_bits, group_size=None,
                   interpret=False, **blk):
    del a_bits
    return expert_lut_gemm_pallas(a_idx, wp, table, sc, bits=w_bits,
                                  group_size=group_size, interpret=interpret,
                                  **blk)


def _lut65k_ref(ap, wp, table):
    return _ref.ref_lut65k_gemm(ap, wp, table)


def _kv_attn_ref(q, kp, k_sc, vp, v_sc, lengths, *, bits=4, bs=512):
    del bs
    return _ref.ref_kv_cache_attention(q, kp, k_sc, vp, v_sc, lengths, bits)


def _kv_attn_pl(q, kp, k_sc, vp, v_sc, lengths, *, bits=4, bs=512,
                interpret=False):
    return kv_cache_attention_pallas(q, kp, k_sc, vp, v_sc, lengths,
                                     bits=bits, bs=bs, interpret=interpret)


def _paged_attn_ref(q, kp, k_sc, vp, v_sc, bt, lengths, *, bits=4):
    return _ref.ref_paged_attention(q, kp, k_sc, vp, v_sc, bt, lengths, bits)


def _paged_attn_pl(q, kp, k_sc, vp, v_sc, bt, lengths, *, bits=4,
                   interpret=False):
    return paged_attention_pallas(q, kp, k_sc, vp, v_sc, bt, lengths,
                                  bits=bits, interpret=interpret)


def _paged_attn_splitkv_ref(q, kp, k_sc, vp, v_sc, bt, lengths, *, bits=4,
                            kv_splits=2):
    return _ref.ref_paged_attention_splitkv(q, kp, k_sc, vp, v_sc, bt,
                                            lengths, bits,
                                            kv_splits=kv_splits)


def _paged_attn_splitkv_pl(q, kp, k_sc, vp, v_sc, bt, lengths, *, bits=4,
                           kv_splits=2, interpret=False, bm=None, bn=None,
                           bk=None):
    # autotuner tile override: bn carries the kv_splits candidate
    del bm, bk
    return paged_attention_splitkv_pallas(
        q, kp, k_sc, vp, v_sc, bt, lengths, bits=bits,
        kv_splits=int(bn) if bn else kv_splits, interpret=interpret)


def _paged_attn_splitkv_tp(role, ax, n, arrays, static):
    """Head-sharded: KV heads split across the mesh axis — q/out on axis 1,
    pools and scales on their KV axis 2, tables/lengths replicated. Pure
    data parallelism over heads, so no collective (reduce=False)."""
    del role
    q = arrays[0]
    KV = q.shape[1]
    if KV % n != 0:
        return None
    return ((P(None, ax), P(None, None, ax), P(None, None, ax),
             P(None, None, ax), P(None, None, ax), P(), P()),
            P(None, ax), False)


def _splitkv_tile_space(m, k, n, static):
    # the tunable knob is kv_splits (threaded through the bn slot); bm/bk
    # are placeholders so the (bm, bn, bk) block contract stays uniform
    return [(1, s, 0) for s in (1, 2, 4, 8, 16)]


# --------------------------------------------------------------------------- #
# The registry
# --------------------------------------------------------------------------- #

register(KernelOp(
    name="lut_gemm",
    ref=_lut_gemm_ref, pallas=_lut_gemm_pl, tp_rule=_lut_gemm_tp,
    tile_space=_matmul_tile_space,
    doc="Paper-faithful product-LUT GEMM: "
        "out[m,n] = sum_k LUT[(w[n,k]<<b)|a[m,k]]. "
        "arrays: (a_idx, w_packed, lut_table, w_scales|None), a_idx the "
        "(M, K) uint8 activation codes"))

register(KernelOp(
    name="lut_gemm_bitsliced",
    ref=_bitsliced_ref, pallas=_bitsliced_pl, tp_rule=_bitsliced_tp,
    tile_space=_matmul_tile_space,
    doc="T-MAC bit-sliced LUT GEMM: per-token subset-sum LUT, one gather "
        "per PAIR of weight planes (coefficients folded into a combined "
        "2^(2g)-entry table), int16 tile accumulate, GEMV tiling for M<=4. "
        "arrays: (a_codes, w_planes, w_scales|None)"))

register(KernelOp(
    name="lut_gemm_bs_fused",
    ref=_bs_fused_ref, pallas=_bs_fused_pl, tp_rule=_bs_fused_tp,
    tile_space=_bs_fused_tile_space,
    doc="Fused-prologue bit-sliced LUT GEMM: per-token activation "
        "quantization (dynamic row amax or a static per-tensor a_sc), the "
        "paired-plane subset-sum core, and the full weight x activation "
        "scale epilogue in one kernel — raw bf16/f32 activations in, "
        "scaled f32 out. arrays: (x, w_planes, w_scales, a_sc|None)"))

register(KernelOp(
    name="dequant_matmul",
    ref=_dequant_matmul_ref, pallas=_dequant_matmul_pl,
    tp_rule=_dequant_matmul_tp, tile_space=_matmul_tile_space,
    doc="TPU-native packed-weight matmul: (a @ dequant(w).T) * scales. "
        "arrays: (a, w_packed, codebook, scales)"))

register(KernelOp(
    name="expert_dequant_matmul",
    ref=_expert_dequant_ref, pallas=_expert_dequant_pl,
    tp_rule=_expert_dequant_matmul_tp, tile_space=_matmul_tile_space,
    doc="Grouped per-expert packed matmul (MoE serving hot-spot). "
        "arrays: (x, w_packed, codebook, scales)"))

register(KernelOp(
    name="expert_lut_gemm",
    ref=_expert_lut_ref, pallas=_expert_lut_pl, tp_rule=_expert_lut_gemm_tp,
    tile_space=_matmul_tile_space,
    doc="Activation-quantized per-expert LUT GEMM (paper-faithful w{b}a{b} "
        "MoE path). arrays: (a_idx, w_packed, lut_table, w_scales|None)"))

register(KernelOp(
    name="lut65k_gemm",
    ref=_lut65k_ref, pallas=None,
    doc="LUT-65k — reference path only (no TPU lowering by design, "
        "DESIGN.md §7). arrays: (a_packed, w_packed, table)"))

register(KernelOp(
    name="kv_cache_attention",
    ref=_kv_attn_ref, pallas=_kv_attn_pl,
    doc="Decode attention over an int8/int4-packed KV cache (fused "
        "dequant). arrays: (q, k_packed, k_sc, v_packed, v_sc, lengths)"))

register(KernelOp(
    name="paged_attention",
    ref=_paged_attn_ref, pallas=_paged_attn_pl,
    doc="Decode attention over a paged packed KV-cache pool via per-"
        "sequence block tables. arrays: (q, k_pool, k_sc, v_pool, v_sc, "
        "block_tables, lengths)"))

register(KernelOp(
    name="paged_attention_splitkv",
    ref=_paged_attn_splitkv_ref, pallas=_paged_attn_splitkv_pl,
    tp_rule=_paged_attn_splitkv_tp, tile_space=_splitkv_tile_space,
    doc="Flash-decoding paged attention: the block table is partitioned "
        "into kv_splits chunks, each folded by its own online softmax into "
        "(acc, m, l) partials, then a fixed-shape lse merge reduces them "
        "exactly. arrays: (q, k_pool, k_sc, v_pool, v_sc, block_tables, "
        "lengths)"))
