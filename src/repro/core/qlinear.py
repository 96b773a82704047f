"""QuantizedDense: the paper's technique as a composable model layer.

Three execution modes, all sharing one parameter pytree:

  train ('qat')     LSQ fake-quant (paper Tab. 1 methodology) on weights and
                    optionally activations; gradients flow via STE; the learned
                    step sizes are parameters. Runs in bf16/f32 — packing is a
                    serving-time transformation.
  serve w2a16       packed sub-byte weights + codebook-LUT dequant + MXU matmul
                    (beyond-paper TPU-native path, kernels/lut_dequant_matmul).
  serve w2a2        the paper-faithful path: activations dynamically quantized
                    to b bits, both operands packed, product-LUT GEMM
                    (kernels/lut_gemm). In the SPMD dry-run this dispatches to
                    the algebraically-identical dequant formulation so GSPMD
                    sees shardable dense HLO (see kernels/ops.py 'ref').

Mixed precision (paper §1, HAWQ-V3 discussion): a ``QuantPolicy`` maps layer
classes -> bits (None = keep bf16), so sensitive layers (router, embeddings)
stay high precision while GEMM-heavy layers drop to 2 bits. ``core/qplan.py``
generalizes the single policy into an ordered tag -> policy table (the
execution plan) and is where kernel-backed serving is opted into: a policy
with ``kernel`` set produces QuantizedWeight leaves that ``models/layers.
dense`` dispatches through the Pallas kernels; a legacy policy (kernel None)
keeps the historical dequant-einsum formulation bit-for-bit.

Everything the serving hot path needs is precomputed OFFLINE at quantize
time and stored in the packed pytree: sub-byte codes (packing scheme
recorded and dispatched explicitly), group-wise scales (per (out, K/G)
along the contraction axis — finer than per-channel at the same bits), the
activation codebook, and the weight x activation product LUT. The jit'd
forward never calls ``product_lut`` or ``uniform_codebook``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
import jax.numpy as jnp

from . import packing, quant
from .lut import product_lut
from repro.kernels import registry as kreg


# --------------------------------------------------------------------------- #
# Policy
# --------------------------------------------------------------------------- #

def _component_parts(component: str) -> list[str]:
    """'tok_embed' -> ['tok_embed', 'tok', 'embed']."""
    return [component] + (component.split("_") if "_" in component else [])


def tag_matches(pattern: str, tag: str) -> bool:
    """True if ``pattern`` matches ``tag`` (shared by QuantPolicy.skip and
    qplan.QuantPlan rules).

    * ``"*"`` matches every tag.
    * Otherwise both are split into path components on ``.``/``/`` and the
      pattern's component sequence must appear as a CONTIGUOUS subsequence
      of the tag's components ('moe.experts' matches
      'blocks.l0.moe.experts.we_gate'). A single-component pattern also
      matches a component's underscore-separated words ('norm' matches
      'final_norm' but not 'w_denorm' — never substrings).
    """
    if pattern == "*":
        return True
    pat = [c for c in re.split(r"[./]", pattern) if c]
    tc = [c for c in re.split(r"[./]", tag) if c]
    if not pat or len(pat) > len(tc):
        return False
    if len(pat) == 1:
        return any(pat[0] in _component_parts(c) for c in tc)
    return any(all(tc[i + j] == pat[j] for j in range(len(pat)))
               for i in range(len(tc) - len(pat) + 1))


def skip_matches(name: str, tag: str) -> bool:
    """Skip-list match: component semantics of ``tag_matches`` (supports
    dotted entries like 'moe.experts'), NOT substrings."""
    return tag_matches(name, tag)


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Per-layer-class quantization policy (mixed precision).

    ``group_size`` switches weight calibration from per-output-channel to
    group-wise along K: one scale per (out, K/G) group (K padded to a
    multiple of G). ``kernel`` opts the layer into kernel-backed serving
    dispatch: None keeps the legacy dequant-einsum forward; 'auto' resolves
    to 'lut_gemm' when a_bits is set, else 'dequant_matmul'; or name one
    explicitly. 'bf16' pins the layer to full precision: such a policy
    never applies, so quantize_tree leaves the weight untouched.

    ``a_scale`` picks how w{b}a{b} activation scales are produced at serve
    time: 'dynamic' (default) computes one scale per token row inside the
    forward; 'static' uses a scale calibrated OFFLINE over sample batches
    (core/calibrate.py + lm.calibrate_act_scales) and stored on the packed
    leaf — no per-token reduction on the hot path. Layers without
    calibration stats fall back to dynamic.
    """
    w_bits: Optional[int] = 2          # None => bf16 layer
    a_bits: Optional[int] = None       # None => weight-only (w2a16)
    signed: bool = True
    nonuniform: bool = False           # k-means codebook instead of uniform
    # layer classes to keep full precision (matched against tag components)
    skip: tuple = ("router", "embed", "norm")
    group_size: Optional[int] = None   # K-group size for scales (None: per-channel)
    # None | 'auto' | any kernels/registry op name ('dequant_matmul',
    # 'lut_gemm', 'lut_gemm_bitsliced', ...)
    kernel: Optional[str] = None
    a_scale: str = "dynamic"           # 'dynamic' | 'static' (calibrated)

    def applies(self, tag: str) -> bool:
        return self.w_bits is not None and self.kernel != "bf16" and not any(
            skip_matches(s, tag) for s in self.skip)

    def policy_for(self, tag: str) -> Optional["QuantPolicy"]:
        """Uniform interface with qplan.QuantPlan."""
        return self if self.applies(tag) else None

    def resolved_kernel(self) -> Optional[str]:
        if self.kernel != "auto":
            return self.kernel
        return "lut_gemm" if self.a_bits is not None else "dequant_matmul"


BF16_POLICY = QuantPolicy(w_bits=None)
W2A16 = QuantPolicy(w_bits=2, a_bits=None)
W2A2 = QuantPolicy(w_bits=2, a_bits=2)
W4A16 = QuantPolicy(w_bits=4, a_bits=None)
W4A8 = QuantPolicy(w_bits=4, a_bits=8)


# --------------------------------------------------------------------------- #
# Packed serving weights
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class QuantizedWeight:
    """Serving-time packed weight for one dense layer.

    packed   : (out, in/f) uint8 — packed codes along K in the natural slot
               layout (``scheme`` 'a', packing.pack). The bit-sliced route
               stores (bits, out, in/g) two's-complement plane patterns
               instead (scheme 'bs', packing.pack_bitplanes_signed)
    codebook : (2^bits,) f32 — *unscaled* levels (uniform ints or k-means)
    scales   : (out,) f32 per-output-channel, or (out, K/G) group-wise when
               ``group_size`` is set (K the padded contraction axis)
    a_levels : (2^a_bits,) f32 activation codebook, precomputed at quantize
               time for w{b}a{b} plans (None otherwise)
    plut     : (2^(bits+a_bits),) f32 product LUT table, precomputed at
               quantize time for w{b}a{b} plans (None otherwise)
    kernel   : serving dispatch — None keeps the legacy dequant-einsum path
               in models/layers.dense; 'dequant_matmul' / 'lut_gemm' route
               through kernels/ops.
    a_sc     : scalar f32 STATIC activation scale, calibrated offline
               (QuantPolicy.a_scale == 'static'); None -> dynamic per-token
    tp       : tensor-parallel role recorded at quantize time — 'col' (packed
               codes + scales shard along out/N), 'row' (shard along the
               packed contraction axis, outputs psum'd) or None (replicate).
               Only honoured when a dist.sharding.use_tp context is active.
    tiles    : autotuned Pallas blocks, a static tuple of (m, bm, bn, bk)
               entries keyed by token-row bucket (kernels/autotune, stamped
               at quantize_tree time — NEVER under jit). Aux data: hashable,
               survives checkpoints via the manifest meta (autotune.
               tile_meta / apply_tile_meta). Empty -> kernel defaults.
    """
    packed: jax.Array
    codebook: jax.Array
    scales: jax.Array
    bits: int
    in_features: int
    out_features: int
    group_size: Optional[int] = None
    a_bits: Optional[int] = None
    scheme: str = "a"
    kernel: Optional[str] = None
    a_levels: Optional[jax.Array] = None
    plut: Optional[jax.Array] = None
    a_sc: Optional[jax.Array] = None
    tp: Optional[str] = None
    tiles: tuple = ()

    def tree_flatten_with_keys(self):
        return (
            (jax.tree_util.GetAttrKey("packed"), self.packed),
            (jax.tree_util.GetAttrKey("codebook"), self.codebook),
            (jax.tree_util.GetAttrKey("scales"), self.scales),
            (jax.tree_util.GetAttrKey("a_levels"), self.a_levels),
            (jax.tree_util.GetAttrKey("plut"), self.plut),
            (jax.tree_util.GetAttrKey("a_sc"), self.a_sc),
        ), (self.bits, self.in_features, self.out_features, self.group_size,
            self.a_bits, self.scheme, self.kernel, self.tp, self.tiles)

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, codebook, scales, a_levels, plut, a_sc = children
        bits, in_f, out_f, group_size, a_bits, scheme, kernel, tp, tiles = aux
        return cls(packed, codebook, scales, bits, in_f, out_f, group_size,
                   a_bits, scheme, kernel, a_levels, plut, a_sc, tp, tiles)

    @property
    def nbytes_packed(self) -> int:
        return self.packed.size * self.packed.dtype.itemsize

    @property
    def k_padded(self) -> int:
        """Padded contraction length recoverable from the packed layout."""
        if self.scheme == "bs":
            return self.packed.shape[-1] * packing.BITPLANE_GROUP
        return self.packed.shape[-1] * packing.PACK_FACTOR[self.bits]

    def unpacked_idx(self) -> jax.Array:
        """(..., out, in_pad) unsigned storage codes for any scheme."""
        if self.scheme == "bs":
            return packing.unpack_bitplanes_signed(self.packed, self.bits)
        return packing.unpack(self.packed, self.bits)


jax.tree_util.register_pytree_with_keys(
    QuantizedWeight,
    QuantizedWeight.tree_flatten_with_keys,
    QuantizedWeight.tree_unflatten)


def _k_multiple(policy: QuantPolicy, tp_shards: int = 1) -> int:
    """Contraction-axis padding unit: the pack factor (or the scale-group
    size, itself a pack-factor multiple), times the TP shard count for
    row-parallel layers — so every shard holds whole packed weight bytes
    and whole scale groups (a group boundary never straddles a shard
    split). Activation codes are never packed, so they add nothing."""
    import math
    m = policy.group_size if policy.group_size is not None \
        else packing.PACK_FACTOR[policy.w_bits]
    kern = policy.resolved_kernel()
    if kern == "lut_gemm_bitsliced":
        # plane patterns group BITPLANE_GROUP codes per byte; activations
        # stay unpacked int8 codes, so that is the only extra constraint
        m = math.lcm(m, packing.BITPLANE_GROUP)
    return m * max(tp_shards, 1)


def _pad_k(wt: jax.Array, multiple: int) -> jax.Array:
    """Pad the contraction axis to a ``multiple`` with zeros (the zero-value
    code dequantizes to exactly 0.0 -> padded columns contribute nothing;
    dequant_weight slices them back off)."""
    pad = (-wt.shape[-1]) % multiple
    if pad:
        cfgpad = [(0, 0)] * (wt.ndim - 1) + [(0, pad)]
        wt = jnp.pad(wt, cfgpad)
    return wt


def _calibrate(wt: jax.Array, bits: int, signed: bool,
               group_size: Optional[int]) -> tuple[jax.Array, jax.Array]:
    """(..., out, K) -> (scales, scales expanded to (..., out, K)).
    Per-channel: scales (..., out). Group-wise: scales (..., out, K/G)."""
    if group_size is None:
        scales = quant.group_scales(wt, bits, None, signed=signed)
        return scales, scales[..., None]
    scales = quant.group_scales(wt, bits, group_size, signed=signed)
    return scales, quant.expand_group_scales(scales, group_size)


def _act_tables(policy: QuantPolicy, w_levels: jax.Array):
    """Precompute the activation codebook + product LUT once, offline, for
    plans that run the paper-faithful w{b}a{b} kernel. The bit-sliced route
    keeps the codebook (the dry-run's dequant formulation gathers it) but
    has no product LUT — its LUT is built from the activations in-kernel."""
    kern = policy.resolved_kernel()
    if policy.a_bits is None or kern not in ("lut_gemm", "lut_gemm_bitsliced"):
        return None, None
    a_levels = quant.uniform_codebook(policy.a_bits, True).levels
    if kern == "lut_gemm_bitsliced":
        return a_levels, None
    plut = product_lut(w_levels, a_levels).table
    return a_levels, plut


def quantize_weight(w: jax.Array, policy: QuantPolicy, *,
                    tp_role: Optional[str] = None, tp_shards: int = 1,
                    a_static: Optional[float] = None) -> QuantizedWeight:
    """Offline weight quantize+pack (paper: 'packing and quantization of
    weights was handled offline'). w: (in, out) -> packed (out, ceil(in/f)).

    With ``policy.group_size`` set, scales are per (out, K/G) group along
    the contraction axis. With ``policy.kernel`` set, the returned leaf also
    carries the precomputed activation codebook and product LUT and is
    dispatched through the Pallas kernels by models/layers.dense.

    ``tp_role``/``tp_shards`` record the tensor-parallel split the tree is
    packed for: 'row' additionally pads K so every one of ``tp_shards``
    shards holds whole packed bytes (both operands) and whole scale groups.
    ``a_static`` is a calibrated static activation scale (stored on the
    leaf; None keeps dynamic per-token quantization).
    """
    bits = policy.w_bits
    assert bits is not None
    G = policy.group_size
    if policy.nonuniform and G is not None:
        raise NotImplementedError("group-wise scales with a k-means codebook")
    mult = _k_multiple(policy, tp_shards if tp_role == "row" else 1)
    wt = _pad_k(w.T.astype(jnp.float32), mult)               # (out, in_pad)
    if policy.nonuniform:
        cb = quant.kmeans_codebook(wt, bits)
        # per-channel scale folded as amax normalisation before codebook fit
        scales = jnp.ones((wt.shape[0],), jnp.float32)
        idx = quant.codebook_quantize(wt, cb)
        levels = cb.levels
    else:
        scales, sfull = _calibrate(wt, bits, policy.signed, G)
        q = quant.quantize(wt, sfull, bits=bits, signed=policy.signed)
        idx = quant.to_index(q, bits, policy.signed)
        levels = quant.uniform_codebook(bits, policy.signed).levels
    a_levels, plut = _act_tables(policy, levels)
    a_sc = None
    if a_static is not None and a_levels is not None:
        a_sc = jnp.asarray(a_static, jnp.float32)
    kern = policy.resolved_kernel() if policy.kernel else None
    if kern == "lut_gemm_bitsliced":
        # the plane decomposition IS the codebook: code value = idx - 2^(b-1)
        assert policy.signed and not policy.nonuniform \
            and policy.a_bits is not None, \
            "bit-sliced route needs signed uniform w{b}a{b} quantization"
        packed, scheme = packing.pack_bitplanes_signed(idx, bits), "bs"
    else:
        packed, scheme = packing.pack(idx, bits), "a"
    return QuantizedWeight(
        packed=packed, codebook=levels,
        scales=scales, bits=bits,
        in_features=w.shape[0], out_features=w.shape[1],
        group_size=G, a_bits=policy.a_bits, scheme=scheme,
        kernel=kern,
        a_levels=a_levels, plut=plut, a_sc=a_sc, tp=tp_role)


def quantize_expert_weight(w: jax.Array, policy: QuantPolicy, *,
                           tp_role: Optional[str] = None,
                           tp_shards: int = 1) -> QuantizedWeight:
    """Offline quantize+pack for stacked expert weights. w: (E, in, out) ->
    packed (E, out, in/f), scales (E, out) per-expert-per-channel or
    (E, out, K/G) group-wise. A 'lut_gemm' plan keeps the LUT route: the
    leaf carries the activation codebook + product LUT and the MoE forward
    runs per-token activation quantization + expert_lut_gemm."""
    bits = policy.w_bits
    assert bits is not None and w.ndim == 3
    G = policy.group_size
    mult = _k_multiple(policy, tp_shards if tp_role == "row" else 1)
    wt = _pad_k(jnp.swapaxes(w, 1, 2).astype(jnp.float32), mult)  # (E, out, in_pad)
    scales, sfull = _calibrate(wt, bits, policy.signed, G)
    q = quant.quantize(wt, sfull, bits=bits, signed=policy.signed)
    idx = quant.to_index(q, bits, policy.signed)
    levels = quant.uniform_codebook(bits, policy.signed).levels
    kern = policy.resolved_kernel() if policy.kernel else None
    a_levels, plut = _act_tables(policy, levels)
    return QuantizedWeight(
        packed=packing.pack(idx, bits), codebook=levels,
        scales=scales, bits=bits, in_features=w.shape[1],
        out_features=w.shape[2], group_size=G,
        a_bits=policy.a_bits if kern == "lut_gemm" else None, kernel=kern,
        a_levels=a_levels, plut=plut, tp=tp_role)


def dequant_weight(qw: QuantizedWeight) -> jax.Array:
    """Full dequantization (codebook gather + per-channel or group scale),
    returned in (in, out) / (E, in, out) orientation for einsum use. This is
    the GSPMD-shardable formulation the dry-run lowers; the Pallas kernels
    fuse the same steps tile-wise in VMEM. (Scheme 'bs' reassembles codes
    from the two's-complement bit planes.)"""
    idx = qw.unpacked_idx().astype(jnp.int32)                    # (..., out, in_pad)
    w = jnp.take(qw.codebook, idx)
    if qw.group_size is not None:
        w = w * quant.expand_group_scales(qw.scales, qw.group_size)
    else:
        w = w * qw.scales[..., None]
    w = w[..., : qw.in_features]                                 # drop K padding
    return jnp.swapaxes(w, -1, -2)                               # (..., in, out)


# --------------------------------------------------------------------------- #
# Forward paths
# --------------------------------------------------------------------------- #

def dense_init(key, in_features: int, out_features: int, *, bias: bool = False,
               dtype=jnp.float32) -> dict:
    k1, _ = jax.random.split(key)
    p = {"w": jax.random.normal(k1, (in_features, out_features), dtype)
             * (1.0 / jnp.sqrt(in_features))}
    if bias:
        p["b"] = jnp.zeros((out_features,), dtype)
    return p


def qat_init(params: dict, policy: QuantPolicy) -> dict:
    """Attach LSQ step-size parameters for QAT."""
    out = dict(params)
    if policy.w_bits is not None:
        out["w_step"] = quant.lsq_init_step(params["w"], policy.w_bits, policy.signed)
    if policy.a_bits is not None:
        out["a_step"] = jnp.asarray(0.05, params["w"].dtype)  # calibrated online
    return out


def dense_apply(params: dict, x: jax.Array, *, policy: QuantPolicy = BF16_POLICY,
                mode: str = "plain") -> jax.Array:
    """x: (..., in) -> (..., out). mode: 'plain' | 'qat'."""
    w = params["w"]
    if mode == "qat" and policy.w_bits is not None:
        w = quant.lsq_fake_quant(w, params["w_step"], policy.w_bits, policy.signed)
        if policy.a_bits is not None:
            x = quant.lsq_fake_quant(x, params["a_step"], policy.a_bits, policy.signed)
    y = jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def tile_for(qw: QuantizedWeight, m: int) -> tuple[int, int, int] | None:
    """Look up an autotuned Pallas block for a token-row count ``m``.

    Static trace-time Python over the leaf's aux ``tiles`` tuple: exact
    bucket first, else the smallest tuned bucket >= m, else the largest.
    A miss (no tiles stamped) returns None -> kernel default blocks. No
    tuning ever happens here — tiles are stamped offline by quantize_tree.
    """
    if not qw.tiles:
        return None
    above = [t for t in qw.tiles if t[0] >= m]
    best = min(above, key=lambda t: t[0]) if above \
        else max(qw.tiles, key=lambda t: t[0])
    return tuple(best[1:4])


def dense_serve(
    qw: QuantizedWeight,
    x: jax.Array,
    *,
    a_bits: Optional[int] = None,
    a_scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    backend: str = "auto",
    block: tuple[int, int, int] | None = None,
) -> jax.Array:
    """Serving forward with packed weights. x: (..., in) -> (..., out).

    a_bits None  -> w{b}a16 path (codebook dequant + MXU matmul), unless the
                    leaf's plan kernel is an a-quantizing route ('lut_gemm' /
                    'lut_gemm_bitsliced' — then qw.a_bits is used).
    a_bits set   -> paper-faithful w{b}a{b}: dynamic activation quant, LUT GEMM.

    The activation codebook and product LUT come from the leaf when they
    were precomputed at quantize time (planned trees); only legacy ad-hoc
    calls construct them here. All kernel calls go through the KernelOp
    registry; ``block`` None falls back to the leaf's autotuned tile for
    this M bucket (tile_for), then to kernel defaults.
    """
    if a_bits is None and qw.kernel in ("lut_gemm", "lut_gemm_bitsliced"):
        a_bits = qw.a_bits
    lead = x.shape[:-1]
    xm = x.reshape(-1, qw.in_features)
    # weights are K-padded to a pack-factor multiple; mirror it on activations
    k_pad = qw.k_padded
    if k_pad != qw.in_features:
        xm = jnp.pad(xm, ((0, 0), (0, k_pad - qw.in_features)))
    # pad LARGE awkward token counts to a multiple of 8: the kernels pick
    # block sizes that DIVIDE M, so e.g. a prime M=251 would degrade to
    # per-row grid programs. M <= 8 already runs as a single block (no pad
    # — decode with few slots must not trace extra rows forever). Zero
    # rows are inert and sliced off.
    n_rows = xm.shape[0]
    if n_rows > 8 and n_rows % 8:
        xm = jnp.pad(xm, ((0, (-n_rows) % 8), (0, 0)))
    if block is None:
        block = tile_for(qw, xm.shape[0])
    G = qw.group_size
    if a_bits is None:
        y = kreg.dispatch(
            "dequant_matmul", xm, qw.packed, qw.codebook, qw.scales,
            bits=qw.bits, group_size=G, backend=backend, block=block,
            tp=qw.tp)
    else:
        # Activation quantization scale. Static (calibrated offline,
        # QuantPolicy.a_scale='static'): one per-tensor scale from the
        # leaf — no reduction on the hot path, trivially batch-independent.
        # Dynamic (default; paper Fig. 7 'Quantization', at row
        # granularity): each row's scale depends only on its own
        # activations, so outputs are batch-composition-independent and
        # prefill+decode stays consistent with the full forward.
        if a_scale is None and qw.a_sc is not None and a_bits == qw.a_bits:
            a_scale = jnp.reshape(qw.a_sc, (1, 1)).astype(jnp.float32)
        if qw.kernel == "lut_gemm_bitsliced" and not (
                qw.tp == "row" and kreg._tp_active(qw.tp) is not None):
            # Fused-prologue T-MAC route (ALL backends, including 'ref' —
            # the op's ref impl IS the optimized CPU formulation): raw
            # activations go straight in; per-token quantization, the
            # paired-plane integer core, and the full scale epilogue run
            # inside the op. ``a_scale`` None means dynamic in-op row amax;
            # the static (1, 1) / explicit scale rides the a_sc slot.
            # Row-TP leaves fall through to the two-step route below — the
            # fused op only column-shards (a K split would change the
            # dynamic scales), while two-step row-shards with one psum.
            y = kreg.dispatch(
                "lut_gemm_bs_fused", xm, qw.packed, qw.scales, a_scale,
                w_bits=qw.bits, a_bits=a_bits, group_size=G,
                backend=backend, block=block, tp=qw.tp)
            y = y[:n_rows]
            if bias is not None:
                y = y + bias
            return y.reshape(*lead, qw.out_features).astype(x.dtype)
        if a_scale is None:
            a_scale, _ = quant.compute_scale_zero_point(
                xm, a_bits, signed=True, axis=0)                    # (M, 1)
        aq = quant.quantize(xm, a_scale, bits=a_bits, signed=True)
        a_idx = quant.to_index(aq, a_bits, True)
        if qw.a_levels is not None and a_bits == qw.a_bits:
            a_levels = qw.a_levels
        else:
            a_levels = quant.uniform_codebook(a_bits, True).levels
        if kreg.resolve_backend(backend) == "ref":
            # Shardable dequant formulation — exactly equal to the LUT GEMM
            # (and to the bit-sliced integer path: both sum the same exact
            # integer products, merely scaled differently in the epilogue).
            a_deq = jnp.take(a_levels, a_idx.astype(jnp.int32))
            w_deq = jnp.take(qw.codebook,
                             qw.unpacked_idx().astype(jnp.int32))
            if G is not None:
                w_deq = w_deq * quant.expand_group_scales(qw.scales, G)
            y = jax.lax.dot_general(a_deq, w_deq, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            y = y * a_scale if G is not None \
                else y * qw.scales[None, :] * a_scale
        elif qw.kernel == "lut_gemm_bitsliced":
            # Two-step T-MAC route (row-TP fallback): the LUT is built from
            # the activation CODES inside the kernel; weights are two's-
            # complement bit planes. aq holds the signed code values
            # directly (int8 carrier). Bit-identical to the fused route
            # per-channel — both sum the same exact integers.
            y = kreg.dispatch(
                "lut_gemm_bitsliced", aq.astype(jnp.int8), qw.packed,
                qw.scales if G is not None else None,
                w_bits=qw.bits, a_bits=a_bits, group_size=G,
                backend=backend, block=block, tp=qw.tp)
            y = y * a_scale if G is not None \
                else y * qw.scales[None, :] * a_scale
        else:
            if qw.plut is not None and a_bits == qw.a_bits:
                table = qw.plut
            else:
                table = product_lut(qw.codebook, a_levels).table
            y = kreg.dispatch(
                "lut_gemm", a_idx, qw.packed, table,
                qw.scales if G is not None else None,
                w_bits=qw.bits, a_bits=a_bits, group_size=G,
                backend=backend, block=block, tp=qw.tp)
            y = y * a_scale if G is not None \
                else y * qw.scales[None, :] * a_scale
    y = y[:n_rows]
    if bias is not None:
        y = y + bias
    return y.reshape(*lead, qw.out_features).astype(x.dtype)
