"""Quickstart: DeepGEMM's LUT idea in ~40 lines.

Build a 2-bit product lookup table, pack weights and activations to 2-bit
codes, and compute a GEMM with *no multiplies on the operands* — every
product comes out of the 16-entry table. Verifies against the float GEMM of
the dequantized operands (they are EQUAL: the LUT is a reparametrization).

Run: PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lut, packing, quant
from repro.kernels import registry

key = jax.random.PRNGKey(0)
M, N, K, BITS = 64, 128, 256, 2

# 1. quantize float operands to 2-bit codes (symmetric, signed)
a = jax.random.normal(key, (M, K))
w = jax.random.normal(jax.random.fold_in(key, 1), (N, K))
a_scale, _ = quant.compute_scale_zero_point(a, BITS, signed=True)
w_scale, _ = quant.compute_scale_zero_point(w, BITS, signed=True)
a_idx = quant.to_index(quant.quantize(a, a_scale, bits=BITS), BITS)
w_idx = quant.to_index(quant.quantize(w, w_scale, bits=BITS), BITS)

# 2. pack the weights 4 codes per byte (16x smaller than f32); the
#    activation codes stay one per byte, as they are made per call
w_packed = packing.pack(w_idx, BITS)
print(f"W: {w.nbytes} B f32  ->  {w_packed.nbytes} B packed "
      f"({w.nbytes // w_packed.nbytes}x)")

# 3. precompute ALL 16 possible products, fused with the dequant scales
#    (paper §5.3: quant->GEMM->dequant collapses into the table)
cb = quant.uniform_codebook(BITS, signed=True)
table = lut.fused_lut(cb, cb, w_scale, a_scale)
print(f"LUT: {table.n_entries} entries, {table.nbytes} bytes")

# 4. GEMM by table lookup (Pallas kernel, interpret mode on CPU), through
#    the KernelOp registry — the one dispatch surface every caller uses
out = registry.dispatch("lut_gemm", a_idx, w_packed, table.table, None,
                        w_bits=table.w_bits, a_bits=table.a_bits,
                        backend="pallas_interpret", block=(64, 128, 256))

# 5. the oracle: dequantize and matmul — must match exactly
a_deq = quant.dequantize(quant.from_index(a_idx, BITS), a_scale)
w_deq = quant.dequantize(quant.from_index(w_idx, BITS), w_scale)
want = a_deq @ w_deq.T
np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                           atol=1e-4)
err = float(jnp.abs(out - a @ w.T).mean() / jnp.abs(a @ w.T).mean())
print(f"LUT GEMM == dequant GEMM  (2-bit quantization error vs fp32: "
      f"{err:.1%})")
print("OK")
