"""Quantized execution plans: the per-layer-class mixed-precision map.

The paper's technique only pays off end-to-end when every layer runs the
format/kernel pair it was packed for (T-MAC, arXiv:2407.00088: fine-grained
group scales + tables staged once offline; FullPack, arXiv:2211.06982:
per-layer sub-byte layout choice). A ``QuantPlan`` is that decision, made
*offline* and threaded from config to kernel to the serving engine:

  config      ``ModelConfig.quant`` holds a QuantPlan (or a legacy
              QuantPolicy, which keeps the historical dequant-einsum path).
  plan        an ORDERED tag -> QuantPolicy table. The first matching rule
              wins; a ``None`` policy keeps the layer bf16. Patterns match
              on path components (see ``tag_matches``), never substrings.
  format      ``quantize_tree`` resolves the plan per tree path and packs
              each covered layer into a QuantizedWeight carrying everything
              the hot path needs precomputed: packed codes (index-ready
              scheme recorded), group-wise scales (per (out, K/G)), the
              activation codebook, and the product LUT.
  kernel      ``models.layers.dense`` dispatches each packed leaf through
              ``kernels/ops``: w{b}a16 -> dequant_matmul, w{b}a{b} ->
              lut_gemm with dynamic activation quantization, bf16 where the
              plan says so. ``plan.backend`` picks 'ref' (GSPMD-shardable
              jnp, the dry-run form), 'pallas_interpret' (CPU correctness)
              or 'pallas' (TPU); 'auto' resolves by platform.

See docs/quantization.md for the full flow and the trade-off discussion.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# tag_matches is defined beside QuantPolicy (its skip list shares the same
# component semantics) and re-exported here as part of the plan API.
from .qlinear import QuantPolicy, tag_matches  # noqa: F401


# --------------------------------------------------------------------------- #
# The plan: an ordered tag -> policy table
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class QuantPlan:
    """Ordered (pattern, QuantPolicy | None) rules; first match wins.

    A ``None`` policy pins the matched layer class to bf16 (the mixed-
    precision skip). ``backend`` is the kernel backend every planned layer
    dispatches with ('auto' | 'ref' | 'pallas_interpret' | 'pallas').
    ``tune`` lists token-row counts (M buckets) to autotune Pallas tile
    sizes for at quantize_tree time (kernels/autotune); the winning blocks
    are stamped on each packed leaf's ``tiles`` aux. Empty -> no tuning,
    kernel default blocks.
    """
    rules: tuple = ()
    backend: str = "auto"
    tune: tuple = ()

    def policy_for(self, tag: str) -> Optional[QuantPolicy]:
        for pattern, pol in self.rules:
            if tag_matches(pattern, tag):
                if pol is None or pol.w_bits is None or pol.kernel == "bf16":
                    return None
                return pol
        return None

    def applies(self, tag: str) -> bool:
        return self.policy_for(tag) is not None

    def describe(self) -> str:
        lines = [f"QuantPlan(backend={self.backend})"]
        for pattern, pol in self.rules:
            if pol is None or pol.w_bits is None:
                lines.append(f"  {pattern:24s} -> bf16")
            else:
                a = f"a{pol.a_bits}" if pol.a_bits else "a16"
                g = f" g{pol.group_size}" if pol.group_size else ""
                lines.append(
                    f"  {pattern:24s} -> w{pol.w_bits}{a}{g} "
                    f"[{pol.kernel or 'auto'}]")
        return "\n".join(lines)


# Layer classes every preset keeps in bf16: routing and embedding layers are
# precision-sensitive (HAWQ-V3 / paper §1 mixed-precision discussion) and
# norms/positions are not GEMMs.
KEEP_BF16 = ("router", "embed", "norm", "lm_head", "pos")


def make_plan(
    w_bits: int = 2,
    a_bits: Optional[int] = None,
    group_size: Optional[int] = None,
    *,
    backend: str = "auto",
    nonuniform: bool = False,
    signed: bool = True,
    a_scale: str = "dynamic",
    kernel: str = "auto",
    keep: tuple = KEEP_BF16,
    rules: tuple = (),
    tune: tuple = (),
) -> QuantPlan:
    """Single-policy plan: keep-list rules first (bf16), then extra ``rules``
    (ordered, highest priority after the keeps), then a catch-all policy.
    ``a_scale='static'`` opts w{b}a{b} layers into calibrated static
    activation scales (see core/calibrate.py). ``kernel`` picks the route
    ('auto' | any kernels/registry op name, e.g. 'lut_gemm_bitsliced');
    ``tune`` lists M buckets to autotune tiles for (see QuantPlan)."""
    default = QuantPolicy(
        w_bits=w_bits, a_bits=a_bits, group_size=group_size, signed=signed,
        nonuniform=nonuniform, kernel=kernel, a_scale=a_scale)
    keep_rules = tuple((pattern, None) for pattern in keep)
    return QuantPlan(rules=keep_rules + tuple(rules) + (("*", default),),
                     backend=backend, tune=tuple(tune))


def _mixed_plan() -> QuantPlan:
    """Example genuinely mixed plan: attention projections at w4a16 (quality-
    sensitive, activation-heavy), MLP/expert GEMMs at paper-faithful w2a2
    with group-64 scales."""
    attn = QuantPolicy(w_bits=4, a_bits=None, group_size=64, kernel="auto")
    return make_plan(2, 2, group_size=64, rules=(("attn", attn),))


PLANS = {
    "bf16": QuantPlan(rules=(("*", None),)),
    "w2a16": make_plan(2),
    "w2a16g64": make_plan(2, group_size=64),
    "w2a16g128": make_plan(2, group_size=128),
    "w2a2": make_plan(2, 2),
    "w2a2g64": make_plan(2, 2, group_size=64),
    "w4a16": make_plan(4),
    "w4a8": make_plan(4, 8),
    "mixed_attn4_mlp2": _mixed_plan(),
    # T-MAC style bit-sliced routes: int8 activations, bit-plane packed
    # weights, int16-accumulating lut_gemm_bitsliced kernel with a decode
    # (M<=4) GEMV specialization. ``tune`` pre-tunes the decode and a
    # prefill-ish M bucket at quantize time.
    "w2a8_bs": make_plan(2, 8, kernel="lut_gemm_bitsliced", tune=(1, 4)),
    "w2a8_bs_g64": make_plan(2, 8, group_size=64,
                             kernel="lut_gemm_bitsliced", tune=(1, 4)),
    "w4a8_bs": make_plan(4, 8, kernel="lut_gemm_bitsliced", tune=(1, 4)),
}


def get_plan(name: str) -> QuantPlan:
    if name not in PLANS:
        raise KeyError(f"unknown plan {name!r}; have {sorted(PLANS)}")
    return PLANS[name]


def resolve(policy_or_plan, tag: str) -> Optional[QuantPolicy]:
    """Uniform per-tag policy resolution for QuantPolicy and QuantPlan (both
    expose ``policy_for``)."""
    return policy_or_plan.policy_for(tag)


def plan_backend(policy_or_plan) -> str:
    return getattr(policy_or_plan, "backend", "auto")
