"""KernelOp registry: the unified dispatch surface (backend resolution,
trace-time counting, block overrides, optional-operand handling) and the
removal guards where the old kernels/ops deprecation shims used to live."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut, packing, quant
from repro.kernels import ops, ref, registry
from repro.obs import metrics as obs_metrics

RNG = np.random.default_rng(0)


def _lut_case(M=4, N=8, K=32, bits=2):
    a_idx = jnp.asarray(RNG.integers(0, 2 ** bits, (M, K)), jnp.uint8)
    w_idx = jnp.asarray(RNG.integers(0, 2 ** bits, (N, K)), jnp.uint8)
    cb = quant.uniform_codebook(bits, signed=True)
    return a_idx, packing.pack(w_idx, bits), lut.product_lut(cb, cb)


def test_registry_lists_all_ops():
    names = registry.op_names()
    for expected in ("lut_gemm", "lut_gemm_bitsliced", "lut_gemm_bs_fused",
                     "dequant_matmul", "expert_dequant_matmul",
                     "expert_lut_gemm", "lut65k_gemm", "kv_cache_attention",
                     "paged_attention"):
        assert expected in names, names
    # every op declares a ref oracle; docs state the positional arity
    for n in names:
        op = registry.get(n)
        assert callable(op.ref) and "arrays:" in op.doc


def test_unknown_op_raises_with_listing():
    with pytest.raises(KeyError, match="lut_gemm"):
        registry.dispatch("no_such_kernel")


def test_dispatch_counts_name_and_backend():
    a_idx, wp, plut = _lut_case()
    with obs_metrics.scoped() as reg:
        registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                          w_bits=plut.w_bits, a_bits=plut.a_bits,
                          backend="ref")
    c = reg.dispatch_counts()
    assert c.get("lut_gemm") == 1 and c.get("lut_gemm:ref") == 1, c


def test_dispatch_counter_labels():
    """The registry records per-(op, backend, m-bucket, bits) labels on the
    unified kernel_dispatch_total counter (docs/observability.md)."""
    a_idx, wp, plut = _lut_case(M=4)
    with obs_metrics.scoped() as reg:
        registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                          w_bits=plut.w_bits, a_bits=plut.a_bits,
                          backend="ref")
    n = reg.get(obs_metrics.KERNEL_DISPATCH, op="lut_gemm", backend="ref",
                m_bucket="4", bits="2")
    assert n == 1, reg.snapshot()["counters"]


def test_ref_and_pallas_backends_agree():
    a_idx, wp, plut = _lut_case()
    r = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                          w_bits=plut.w_bits, a_bits=plut.a_bits,
                          backend="ref")
    p = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                          w_bits=plut.w_bits, a_bits=plut.a_bits,
                          backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


def test_block_override_changes_grid_not_result():
    a_idx, wp, plut = _lut_case(M=8, N=16, K=128)
    want = ref.ref_lut_gemm(a_idx, wp, plut)
    for block in [(8, 16, 64), (4, 8, 32), (2, 16, 128)]:
        got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                                w_bits=plut.w_bits, a_bits=plut.a_bits,
                                backend="pallas_interpret", block=block)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_none_operand_slots_are_reinserted():
    """Optional operands (group scales) pass positionally as None and the
    impl still sees its full arity — grouped vs ungrouped both dispatch."""
    a_idx, wp, plut = _lut_case(M=4, N=8, K=32)
    sc = jnp.asarray(RNG.random((8, 32 // 8)) + 0.05, jnp.float32)
    got = registry.dispatch("lut_gemm", a_idx, wp, plut.table, sc,
                            w_bits=plut.w_bits, a_bits=plut.a_bits,
                            group_size=8, backend="pallas_interpret")
    want = ref.ref_lut_gemm(a_idx, wp, plut, w_scales=sc, group_size=8)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=1e-5)


def test_tile_space_declared_for_matmul_ops():
    for n in ("lut_gemm", "lut_gemm_bitsliced", "lut_gemm_bs_fused",
              "dequant_matmul"):
        space = registry.get(n).tile_space(1, 1024, 1024, {})
        assert space and all(len(b) == 3 for b in space)
        assert all(b[0] == 1 for b in space)    # GEMV candidates keep bm=M


def test_duplicate_registration_rejected():
    op = registry.get("lut_gemm")
    with pytest.raises(AssertionError, match="duplicate"):
        registry.register(op)


# --------------------------------------------------------------------------- #
# Removal guards: the PR 6/7 kernels/ops deprecation shims are GONE. Stale
# imports must fail loudly at the first attribute access, with the error
# pointing at registry.dispatch / obs.metrics — not silently half-work.
# --------------------------------------------------------------------------- #

def test_ops_wrappers_removed_with_pointer():
    for name in ("lut_gemm", "dequant_matmul", "lut65k_gemm",
                 "expert_dequant_matmul", "expert_lut_gemm",
                 "kv_cache_attention", "paged_attention"):
        with pytest.raises(AttributeError, match="registry.dispatch"):
            getattr(ops, name)


def test_ops_counter_reexports_removed_with_pointer():
    for name in ("DISPATCH_COUNTS", "dispatch_counts",
                 "reset_dispatch_counts"):
        with pytest.raises(AttributeError, match="obs.metrics"):
            getattr(ops, name)
    with pytest.raises(AttributeError, match="no attribute"):
        ops.never_existed


def test_registry_counter_shims_removed():
    """The registry module no longer carries the global-counter mirror; the
    obs metrics registry is the single source of dispatch counts (scoped
    MetricsRegistry.dispatch_counts() is the supported read)."""
    for name in ("DISPATCH_COUNTS", "dispatch_counts",
                 "reset_dispatch_counts"):
        assert not hasattr(registry, name), name
    a_idx, wp, plut = _lut_case()
    with obs_metrics.scoped() as reg:
        registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                          w_bits=plut.w_bits, a_bits=plut.a_bits,
                          backend="ref")
        # isolated scopes (the autotuner's probe mode) stay invisible
        with obs_metrics.scoped(isolate=True):
            registry.dispatch("lut_gemm", a_idx, wp, plut.table, None,
                              w_bits=plut.w_bits, a_bits=plut.a_bits,
                              backend="ref")
    c = reg.dispatch_counts()
    assert c.get("lut_gemm") == 1 and c.get("lut_gemm:ref") == 1, c
