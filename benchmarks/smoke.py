"""CI smoke benchmark: exercise the LUT GEMM kernel path end to end in
well under two minutes and emit a machine-readable JSON result.

Covers the paper's pipeline at reduced shapes — activation quantize+pack,
product-LUT construction, LUT GEMM vs. the dequant GEMM reference (exact
equality, the paper's central claim) — plus wall-time per stage so the CI
artifact seeds a BENCH_*.json perf trajectory that later PRs append to.
"""

import json
import os
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lut, packing, quant
from repro.kernels import ref

from .common import timeit

# (M, K, N) LUT-GEMM shapes: a decode-ish skinny GEMM and two square-ish ones
_SHAPES = [(8, 512, 512), (64, 1024, 1024), (16, 2048, 512)]


def _one_shape(m: int, k: int, n: int, bits: int) -> dict:
    f = packing.PACK_FACTOR[bits]
    rng = np.random.default_rng(0)
    a_idx = jnp.asarray(rng.integers(0, 2 ** bits, (m, k)), jnp.uint8)
    w_idx = jnp.asarray(rng.integers(0, 2 ** bits, (n, k)), jnp.uint8)
    cb = quant.uniform_codebook(bits, True)

    plut = lut.product_lut(cb, cb)

    # AOT-compile every candidate BEFORE any timing: first-call jit compile
    # must never land inside the timed window (it is orders of magnitude
    # larger than a kernel run and used to pollute the lut-vs-dequant
    # comparison this artifact gates). Compile cost is reported separately.
    t0 = time.perf_counter()
    pack = jax.jit(lambda x: packing.pack(x, bits)).lower(a_idx).compile()
    wpack = jax.jit(lambda x: packing.pack(x, bits)).lower(w_idx).compile()
    ap, wp = pack(a_idx), wpack(w_idx)
    gemm = jax.jit(lambda a, w: ref.ref_lut_gemm(a, w, plut)) \
        .lower(a_idx, wp).compile()
    dq = jax.jit(lambda a, w: ref.ref_dequant_gemm(
        a, w, cb.levels, cb.levels, bits)).lower(a_idx, wp).compile()
    t_compile = time.perf_counter() - t0

    got = gemm(a_idx, wp)
    want = dq(a_idx, wp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    t_pack = timeit(pack, a_idx)
    t_lut = timeit(gemm, a_idx, wp)
    t_dq = timeit(dq, a_idx, wp)
    return {
        "m": m, "k": k, "n": n, "bits": bits, "pack_factor": f,
        "lut_gemm_exact": True,
        "pack_s": t_pack,
        "lut_gemm_s": t_lut,
        "dequant_gemm_s": t_dq,
        "compile_s": round(t_compile, 4),
        "gemm_gops": 2.0 * m * k * n / 1e9,
    }


def run(json_out: str = "BENCH_smoke.json") -> dict:
    t0 = time.time()
    rows = [_one_shape(m, k, n, bits)
            for (m, k, n) in _SHAPES for bits in (2, 4)]
    result = {
        "benchmark": "smoke",
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
    print(f"[smoke] {len(rows)} shapes in {result['total_s']}s -> {json_out}")
    return result
