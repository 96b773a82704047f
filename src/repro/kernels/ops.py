"""REMOVED wrapper module — superseded by ``repro.kernels.registry``.

PR 6 replaced the hand-written kernel wrappers that lived here with the
declarative ``KernelOp`` registry and left DeprecationWarning shims behind;
this PR deletes the shims. The module itself stays importable so stale
``from repro.kernels import ops`` lines fail at the first ATTRIBUTE access
with a pointer to the replacement, not with a bare ImportError at a
distance from the offending call.

Every call site is one mechanical rewrite away::

    from repro.kernels import registry as kr
    kr.dispatch("lut_gemm", a_idx, w_packed, lut.table, w_scales,
                w_bits=..., a_bits=..., backend=..., tp=...)

Dispatch counters moved to ``repro.obs.metrics``: ``scoped()`` for isolated
reads, ``global_registry().dispatch_counts()`` for the process view.
"""

from __future__ import annotations

# old name -> replacement spelling, shown verbatim in the error message
_REMOVED = {
    "lut_gemm": 'registry.dispatch("lut_gemm", a_idx, w_packed, '
                "lut.table, w_scales, w_bits=..., a_bits=..., ...)",
    "dequant_matmul": 'registry.dispatch("dequant_matmul", a, w_packed, '
                      "codebook, scales, bits=..., ...)",
    "lut65k_gemm": 'registry.dispatch("lut65k_gemm", a_packed, w_packed, '
                   'table, backend="ref")',
    "expert_dequant_matmul": 'registry.dispatch("expert_dequant_matmul", '
                             "x, w_packed, codebook, scales, bits=..., ...)",
    "expert_lut_gemm": 'registry.dispatch("expert_lut_gemm", a_idx, '
                       "w_packed, lut.table, w_scales, w_bits=..., ...)",
    "kv_cache_attention": 'registry.dispatch("kv_cache_attention", q, '
                          "k_packed, k_sc, v_packed, v_sc, lengths, ...)",
    "paged_attention": 'registry.dispatch("paged_attention", q, k_pool, '
                       "k_sc, v_pool, v_sc, block_tables, lengths, ...)",
    "DISPATCH_COUNTS": "repro.obs.metrics.global_registry()"
                       ".dispatch_counts()",
    "dispatch_counts": "repro.obs.metrics.global_registry()"
                       ".dispatch_counts()",
    "reset_dispatch_counts": "repro.obs.metrics.global_registry()"
                             ".clear(obs.metrics.KERNEL_DISPATCH)",
    "_resolve": "repro.kernels.registry.resolve_backend",
    "_tp_active": "repro.kernels.registry._tp_active",
    "_count": "repro.kernels.registry._count",
}

__all__: list[str] = []


def __getattr__(name: str):
    if name in _REMOVED:
        repl = _REMOVED[name]
        if not repl.startswith("repro."):
            repl = f"repro.kernels.{repl}"
        raise AttributeError(
            f"repro.kernels.ops.{name} was removed; use {repl} instead")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
