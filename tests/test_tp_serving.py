"""Tensor-parallel serving engine on an 8-fake-device mesh.

Mirrors tests/test_dist.py: every mesh test runs in a subprocess with its own
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the fake-device
count never leaks into the single-device tests.

Covered contracts (ISSUE 5 acceptance):
  * bf16 pools: the TP engine's greedy output is TOKEN-IDENTICAL to the
    single-device engine (qwen + gemma3 local/global), including under the
    radix prefix cache and batched prefill.
  * planned w2a2: run-to-run deterministic through the shard_map'd LUT
    kernels, with a nonzero lut_gemm dispatch count.
  * per-device weight bytes ~ 1/8 of the replicated footprint.
  * zero steady-state recompiles (the two-jitted-function invariant holds
    with a mesh).
"""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduce_for_smoke
    from repro.core import qplan
    from repro.launch.mesh import make_tp_mesh
    from repro.models import lm
    from repro.serving import Engine, Request

    def run_engine(cfg, params, mesh, gen=8, n_req=4, **kw):
        rng = np.random.default_rng(1)
        e = Engine(cfg, params, n_slots=2, max_len=64, block_size=8,
                   chunk_size=16, mesh=mesh, **kw)
        prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (int(n),)),
                              np.int32) for n in rng.integers(4, 40, n_req)]
        reqs = [Request(uid=i, prompt=jnp.asarray(p), max_new=gen)
                for i, p in enumerate(prompts)]
        for r in reqs:
            e.submit(r)
        c0 = None
        while e.queue or any(s.state != 0 for s in e.slots):
            e.step()
            if c0 is None and e.decode_steps >= 2:
                c0 = e.n_compiles()
        return [r.out for r in reqs], e, c0
"""


def run_in_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_PRELUDE) + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=1200)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_tp_engine_token_identical_bf16():
    """qwen + gemma3: TP-8 greedy output == single-device greedy output, and
    per-device weight bytes drop to ~1/8."""
    run_in_subprocess("""
        mesh = make_tp_mesh(8)
        for arch in ("qwen1.5-0.5b", "gemma3-12b"):
            cfg = reduce_for_smoke(get_config(arch))
            params = lm.init_params(jax.random.PRNGKey(0), cfg, mode="plain")
            o1, e1, _ = run_engine(cfg, params, None)
            o8, e8, c0 = run_engine(cfg, params, mesh)
            assert o1 == o8, (arch, o1, o8)
            ratio = e8.per_device_weight_bytes() / e1.per_device_weight_bytes()
            assert ratio < 0.25, (arch, ratio)
            assert e8.n_compiles() == c0, (arch, c0, e8.n_compiles())
        print("tp token identity OK")
    """)


def test_tp_engine_with_radix_and_batched_prefill():
    """Prefix sharing + batched prefill keep token identity on the mesh —
    host-side block accounting is untouched by the device-side sharding."""
    run_in_subprocess("""
        cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
        params = lm.init_params(jax.random.PRNGKey(0), cfg, mode="plain")
        o1, _, _ = run_engine(cfg, params, None)
        o8, e8, _ = run_engine(cfg, params, make_tp_mesh(8),
                               prefix_cache=True, prefill_batch=2)
        assert o1 == o8, (o1, o8)
        assert e8.radix is not None
        print("tp radix identity OK")
    """)


def test_tp_quantized_engine_deterministic():
    """Planned w2a2 tree packed for tp=8: the shard_map'd LUT kernels are
    run-to-run deterministic, lut_gemm actually dispatches, and the packed
    leaves carry their TP roles."""
    run_in_subprocess("""
        from repro.core.qlinear import QuantizedWeight
        from repro.obs import metrics as obs_metrics
        cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
        qcfg = dataclasses.replace(cfg, quant=qplan.get_plan("w2a2"))
        params = lm.init_params(jax.random.PRNGKey(0), cfg, mode="plain")
        qp = lm.quantize_tree(params, qcfg, tp=8)
        roles = [l.tp for l in jax.tree.leaves(
                     qp, is_leaf=lambda x: isinstance(x, QuantizedWeight))
                 if isinstance(l, QuantizedWeight)]
        assert "col" in roles and "row" in roles, roles
        mesh = make_tp_mesh(8)
        with obs_metrics.scoped() as reg:
            q1, _, _ = run_engine(qcfg, qp, mesh, gen=4, n_req=3)
        assert reg.dispatch_counts().get("lut_gemm", 0) > 0
        q2, _, _ = run_engine(qcfg, qp, mesh, gen=4, n_req=3)
        assert q1 == q2, (q1, q2)
        print("tp quantized determinism OK")
    """)


def test_tp_sharded_kernels_match_unsharded():
    """shard_map'd lut_gemm / dequant_matmul / expert ops == their unsharded
    outputs (col exactly; row up to psum reassociation)."""
    run_in_subprocess("""
        from repro.core import packing, quant
        from repro.core.lut import product_lut
        from repro.dist import sharding as Sh
        from repro.kernels import registry as kops
        from repro.launch.mesh import make_cpu_mesh
        mesh = make_cpu_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        M, N, K, b, G, E = 8, 64, 64, 2, 8, 2
        lv = quant.uniform_codebook(b, True).levels
        lut = product_lut(lv, lv)
        a_idx = jnp.asarray(rng.integers(0, 4, (M, K)), jnp.uint8)
        w_idx = jnp.asarray(rng.integers(0, 4, (N, K)), jnp.uint8)
        wp = packing.pack(w_idx, b)
        sc = jnp.asarray(rng.random((N, K // G)), jnp.float32)
        ea = jnp.asarray(rng.integers(0, 4, (E, M, K)), jnp.uint8)
        ew = jnp.asarray(rng.integers(0, 4, (E, N, K)), jnp.uint8)
        ewp = packing.pack(ew, b)
        base = kops.dispatch("lut_gemm", a_idx, wp, lut.table, sc,
                             w_bits=b, a_bits=b, group_size=G,
                             backend="pallas_interpret")
        ebase = kops.dispatch("expert_lut_gemm", ea, ewp, lut.table, None,
                              w_bits=b, a_bits=b,
                              backend="pallas_interpret")
        for role, tol in (("col", 0.0), ("row", 1e-4)):
            def f(a_idx, wp, sc):
                with Sh.use_tp(mesh):
                    return kops.dispatch("lut_gemm", a_idx, wp, lut.table, sc,
                                         w_bits=b, a_bits=b, group_size=G,
                                         backend="pallas_interpret", tp=role)
            got = jax.jit(f)(a_idx, wp, sc)
            np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                       atol=max(tol, 1e-12))
            def g(ea, ewp):
                with Sh.use_tp(mesh):
                    return kops.dispatch("expert_lut_gemm", ea, ewp,
                                         lut.table, None, w_bits=b, a_bits=b,
                                         backend="pallas_interpret", tp=role)
            egot = jax.jit(g)(ea, ewp)
            np.testing.assert_allclose(np.asarray(egot), np.asarray(ebase),
                                       atol=max(tol, 1e-12))
        print("sharded kernels OK")
    """)


def test_tp_nondividing_shapes_fall_back():
    """Shapes that do not divide the mesh axis run unsharded (never error),
    and quantize_tree refuses the col role when out does not divide."""
    run_in_subprocess("""
        from repro.core import packing, quant
        from repro.core.lut import product_lut
        from repro.core.qlinear import QuantPolicy, quantize_weight
        from repro.dist import sharding as Sh
        from repro.kernels import registry as kops
        from repro.launch.mesh import make_cpu_mesh
        mesh = make_cpu_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        b = 2
        lv = quant.uniform_codebook(b, True).levels
        lut = product_lut(lv, lv)
        a_idx = jnp.asarray(rng.integers(0, 4, (4, 12)), jnp.uint8)
        w_idx = jnp.asarray(rng.integers(0, 4, (6, 12)), jnp.uint8)   # N=6 !% 8
        wp = packing.pack(w_idx, b)
        base = kops.dispatch("lut_gemm", a_idx, wp, lut.table, None,
                             w_bits=b, a_bits=b, backend="pallas_interpret")
        def f(a_idx, wp):
            with Sh.use_tp(mesh):
                return kops.dispatch("lut_gemm", a_idx, wp, lut.table, None,
                                     w_bits=b, a_bits=b,
                                     backend="pallas_interpret", tp="col")
        np.testing.assert_array_equal(np.asarray(jax.jit(f)(a_idx, wp)),
                                      np.asarray(base))
        # col role refused when out % tp != 0; row pads K to the shard split
        w = jnp.asarray(rng.standard_normal((16, 6)), jnp.float32)
        qw = quantize_weight(w, QuantPolicy(w_bits=2, a_bits=2, kernel="auto"),
                             tp_role=None, tp_shards=8)
        assert qw.tp is None
        qr = quantize_weight(w.T, QuantPolicy(w_bits=2, a_bits=2,
                                              group_size=4, kernel="auto"),
                             tp_role="row", tp_shards=8)
        K = qr.packed.shape[-1] * packing.PACK_FACTOR[2]
        assert (K // 4) % 8 == 0, K   # whole scale groups per shard
        print("fallback OK")
    """)
