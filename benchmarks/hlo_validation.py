"""Roofline-parser validation: the loop-aware HLO dot-FLOP counter vs XLA's
cost_analysis on models where both are trustworthy (no scans / unroll-safe),
plus the scan case where cost_analysis is known to undercount."""

import dataclasses

import jax
import jax.numpy as jnp

from repro.analysis import roofline as RL
from repro.configs import get_config, reduce_for_smoke
from repro.models import lm

from .common import emit


def run():
    rows = []

    # case 1: scan of 8 matmuls — parser must match the unrolled reference
    def body(x, w):
        return jnp.tanh(x @ w), None

    def f_scan(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def f_unroll(x, ws):
        for i in range(8):
            x, _ = body(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    c_s = jax.jit(f_scan).lower(x, ws).compile()
    c_u = jax.jit(f_unroll).lower(x, ws).compile()
    parsed = RL.parse_hlo(c_s.as_text()).dot_flops
    ref_flops = c_u.cost_analysis()["flops"]
    rows.append({"case": "scan8-matmul",
                 "xla_cost_analysis_flops": c_s.cost_analysis()["flops"],
                 "unrolled_reference_flops": ref_flops,
                 "loop_aware_parser_flops": parsed,
                 "parser_vs_ref": round(parsed / ref_flops, 4)})

    # case 2: reduced LM forward+loss (single superblock -> trip counts 1)
    key = jax.random.PRNGKey(0)
    for arch in ("qwen1.5-0.5b", "rwkv6-1.6b"):
        cfg = dataclasses.replace(reduce_for_smoke(get_config(arch)),
                                  remat="none")
        params = lm.init_params(key, cfg, mode="plain")
        tokens = jnp.ones((2, 32), jnp.int32)

        def fwd(p, t):
            h, _ = lm.forward(p, cfg, t)
            return lm.chunked_ce_loss(p, cfg, h, t)

        comp = jax.jit(fwd).lower(params, tokens).compile()
        parsed = RL.parse_hlo(comp.as_text())
        xla = comp.cost_analysis()["flops"]
        rows.append({"case": f"{arch}-fwd-loss",
                     "xla_cost_analysis_flops": xla,
                     "unrolled_reference_flops": "",
                     "loop_aware_parser_flops": parsed.dot_flops,
                     "parser_vs_ref": round(parsed.dot_flops / xla, 4)})
    emit("hlo_parser_validation", rows)
    return rows
