"""Decode-shape kernel benchmark: does LUT-GEMM actually WIN?

ROADMAP item 1: `BENCH_smoke.json` shows the product-LUT formulation merely
tying dequant-then-GEMM. This benchmark times the dense kernel routes of the
registry at the shapes that matter for serving — decode GEMVs (M in {1, 4})
over the qwen1.5-0.5b projection sizes — and emits ``BENCH_kernels.json``
with the headline ratios CI gates on: ``bitsliced_vs_dequant`` (> 1 means
the T-MAC bit-sliced route beats dequant-then-matmul) and ``fused_vs_bf16``
(> 1 means the fused-prologue w2 route beats the full-precision bf16
matmul it replaces — the paper's actual claim).

Routes (all jit'd 'ref' formulations — the XLA:CPU forms a user of this
container actually runs; every fn is AOT-compiled before timing):

  bf16_matmul          x @ w in bf16, the unquantized layer being replaced
  dequant_matmul       codebook-dequantize the packed weights, f32 matmul
  lut_gemm             product-LUT gather (paper's original formulation)
  lut_gemm_bitsliced   per-token subset-sum LUT + one gather per PAIR of
                       bit-planes (T-MAC): ceil(b/2) gathers replace K MACs
  lut_gemm_bs_fused    the serving route: raw bf16 activations in,
                       per-token quantization fused into the prologue

The bit-sliced route wins at decode because its LUT build is O(M*K/g*2^g)
— trivial at M<=4 — after which each of the ceil(b/2)*N*K/g gathers
amortizes g=4 multiply-adds (the 256-entry paired table folds two planes
into one gather), while dequant still pays the full K-length f32 FMA per
output AND the dequantized weight materialization. bf16 loses the M=1 GEMV
outright on XLA:CPU (no fast bf16 GEMV path); at M=4 Eigen's batched bf16
GEMM recovers, so only the M=1 fused rows are CI-gated against bf16 and
M=4 is reported as a trendline (same boundary PR 6 drew for dequant).

Each route is timed back-to-back (median of 7 after AOT warmup), the same
per-route regime the PR-6 gate values were calibrated in. Interleaving the
routes within a round was tried and rejected: alternating five working
sets (the bf16 weights alone are K*N*2 bytes) turns the measurement into
a cache-eviction contest — the down-projection rows swung 1.5x run-to-run
— whereas back-to-back repetition matches steady-state decode, where one
layer's packed planes stay resident across consecutive tokens.
"""

import json
import os
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import lut, packing, quant
from repro.kernels import ref

_M = (1, 4)                       # decode: single token / small slot batch
_BITS = (2, 4)


def _proj_shapes():
    """(K, N) pairs of the qwen1.5-0.5b MLP projections (d_model=1024,
    d_ff=2816): up/gate, down, and the square attention projection."""
    cfg = get_config("qwen1.5-0.5b")
    d, f = cfg.d_model, cfg.d_ff
    return [(d, d), (d, f), (f, d)]


def _aot(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _time_routes(fns_args, warmup: int = 2, iters: int = 7):
    """Median wall-time seconds per route, each route's iterations run
    back-to-back (see module docstring for why not interleaved)."""
    out = []
    for fn, args in fns_args:
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        out.append(float(np.median(ts)))
    return out


def _one(m: int, k: int, n: int, bits: int) -> dict:
    rng = np.random.default_rng(0)
    a_f32 = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    a_bf = a_f32.astype(jnp.bfloat16)
    a_i8 = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w_bf = jnp.asarray(rng.standard_normal((k, n)), jnp.bfloat16)
    w_idx = jnp.asarray(rng.integers(0, 2 ** bits, (n, k)), jnp.uint8)
    cb = quant.uniform_codebook(bits, True)
    scales = jnp.asarray(np.abs(rng.standard_normal((n,))) + 0.05,
                         jnp.float32)

    wp = packing.pack(w_idx, bits)
    planes = packing.pack_bitplanes_signed(w_idx, bits)
    a_idx = jnp.asarray(rng.integers(0, 2 ** bits, (m, k)), jnp.uint8)
    plut = lut.product_lut(cb, cb)

    bf = _aot(lambda a, w: a @ w, a_bf, w_bf)
    dq = _aot(lambda a, w: ref.ref_dequant_matmul(
        a, w, cb.levels, scales, bits), a_f32, wp)
    lg = _aot(lambda a, w: ref.ref_lut_gemm(a, w, plut), a_idx, wp)
    bs = _aot(lambda a, w: ref.ref_lut_gemm_bitsliced(a, w, bits=bits),
              a_i8, planes)
    fu = _aot(lambda a, w, sc: ref.ref_lut_gemm_bs_fused(
        a, w, sc, w_bits=bits), a_bf, planes, scales)

    t_bf, t_dq, t_lg, t_bs, t_fu = _time_routes([
        (bf, (a_bf, w_bf)),
        (dq, (a_f32, wp)),
        (lg, (a_idx, wp)),
        (bs, (a_i8, planes)),
        (fu, (a_bf, planes, scales)),
    ])
    return {
        "m": m, "k": k, "n": n, "bits": bits,
        "bf16_matmul_s": t_bf,
        "dequant_matmul_s": t_dq,
        "lut_gemm_s": t_lg,
        "lut_gemm_bitsliced_s": t_bs,
        "lut_gemm_bs_fused_s": t_fu,
        "bitsliced_vs_dequant": round(t_dq / t_bs, 3),
        "bitsliced_vs_bf16": round(t_bf / t_bs, 3),
        "fused_vs_dequant": round(t_dq / t_fu, 3),
        "fused_vs_bf16": round(t_bf / t_fu, 3),
        "lut_vs_dequant": round(t_dq / t_lg, 3),
    }


def run(json_out: str = "BENCH_kernels.json") -> dict:
    t0 = time.time()
    rows = [_one(m, k, n, bits)
            for (k, n) in _proj_shapes() for m in _M for bits in _BITS]
    result = {
        "benchmark": "kernels_decode",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "total_s": round(time.time() - t0, 2),
        "results": rows,
    }
    out_dir = os.path.dirname(json_out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(json_out, "w") as fh:
        json.dump(result, fh, indent=1)
    w2 = [r for r in rows if r["bits"] == 2]
    w4 = [r for r in rows if r["bits"] == 4]
    print(f"[kernels] {len(rows)} rows in {result['total_s']}s; "
          f"worst w2 bitsliced_vs_dequant = "
          f"{min(r['bitsliced_vs_dequant'] for r in w2)}x; "
          f"worst w2 m=1 fused_vs_bf16 = "
          f"{min(r['fused_vs_bf16'] for r in w2 if r['m'] == 1)}x; "
          f"worst w4 bitsliced_vs_dequant = "
          f"{min(r['bitsliced_vs_dequant'] for r in w4)}x -> {json_out}")
    return result
