"""Weight kernels compiled by Mosaic for a described TPU v5e chip.

Interpret mode accepts blocks and ops the chip's compiler refuses, so the
serving kernels are also lowered and compiled here for one chip of a
``v5e:2x2`` topology, at the qwen1.5-0.5b projection widths and the token
row counts the engine feeds them: decode slots (8), prefill rows (256) and
a prefill padded to a multiple of 8 (264). Nothing runs; a pass means the
compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every xdist worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.kernels import registry

WIDTHS = [(1024, 1024), (1024, 2816), (2816, 1024)]      # (K, N)
ROWS = [8, 256, 264]

# What Mosaic says about the bit-sliced kernels: they gather from an
# in-VMEM table with jnp.take, and the one-hot lookup reshapes a 3D index
# tile across lanes.
BS_TAKE = "Shape mismatch in input, indices and output"
ONEHOT = "infer-vector-layout: unsupported shape cast"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_op(one_chip, op, shapes, **static):
    """Compile ``registry.dispatch(op, ...)`` on the pallas backend for
    operands of ``shapes`` ((shape, dtype) or None per slot)."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    fn = jax.jit(lambda *xs: registry.dispatch(op, *xs, backend="pallas",
                                               **static))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k,n", WIDTHS)
@pytest.mark.parametrize("bits", [2, 4])
def test_dequant_matmul_compiles(one_chip, bits, k, n, m):
    f = packing.PACK_FACTOR[bits]
    hlo = _compile_op(one_chip, "dequant_matmul",
                      [((m, k), jnp.bfloat16), ((n, k // f), jnp.uint8),
                       ((2 ** bits,), jnp.float32), ((n,), jnp.float32)],
                      bits=bits)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bits,group", [(2, 128), (4, 64)])
def test_dequant_matmul_grouped_compiles(one_chip, bits, group):
    k, n, m = 2816, 1024, 264
    f = packing.PACK_FACTOR[bits]
    hlo = _compile_op(one_chip, "dequant_matmul",
                      [((m, k), jnp.bfloat16), ((n, k // f), jnp.uint8),
                       ((2 ** bits,), jnp.float32),
                       ((n, k // group), jnp.float32)],
                      bits=bits, group_size=group)
    assert "tpu_custom_call" in hlo


def test_expert_dequant_matmul_compiles(one_chip):
    e, m, k, n, bits = 4, 64, 1024, 2816, 2
    hlo = _compile_op(one_chip, "expert_dequant_matmul",
                      [((e, m, k), jnp.bfloat16),
                       ((e, n, k // 4), jnp.uint8),
                       ((2 ** bits,), jnp.float32), ((e, n), jnp.float32)],
                      bits=bits)
    assert "tpu_custom_call" in hlo


def _lut_operands(m, k, n, w_bits, a_bits, group=None):
    f = packing.PACK_FACTOR[w_bits]
    return [((m, k), jnp.uint8), ((n, k // f), jnp.uint8),
            ((2 ** (w_bits + a_bits),), jnp.float32),
            None if group is None else ((n, k // group), jnp.float32)]


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k,n", WIDTHS)
def test_lut_gemm_compiles(one_chip, k, n, m):
    hlo = _compile_op(one_chip, "lut_gemm", _lut_operands(m, k, n, 2, 2),
                      w_bits=2, a_bits=2)
    assert "tpu_custom_call" in hlo


# K=1024 runs two K steps of 512 codes (eight groups of 64 each); K=2816
# has no 512-multiple divisor and runs the whole row in one step
@pytest.mark.parametrize("k,n,group", [(1024, 2816, 64), (2816, 1024, 128)])
def test_lut_gemm_grouped_compiles(one_chip, k, n, group):
    hlo = _compile_op(one_chip, "lut_gemm",
                      _lut_operands(264, k, n, 2, 2, group),
                      w_bits=2, a_bits=2, group_size=group)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", [8, 256])
def test_lut_gemm_w4a8_compiles(one_chip, m):
    """The widest product table a plan uses (w4a8: 4096 entries in SMEM,
    256-deep select chains per weight level)."""
    hlo = _compile_op(one_chip, "lut_gemm", _lut_operands(m, 1024, 1024, 4, 8),
                      w_bits=4, a_bits=8)
    assert "tpu_custom_call" in hlo


def test_expert_lut_gemm_compiles(one_chip):
    e, m, k, n = 4, 64, 1024, 2816
    hlo = _compile_op(one_chip, "expert_lut_gemm",
                      [((e, m, k), jnp.uint8), ((e, n, k // 4), jnp.uint8),
                       ((16,), jnp.float32), None],
                      w_bits=2, a_bits=2)
    assert "tpu_custom_call" in hlo


class Refused(Exception):
    """Mosaic refused a kernel with the words the xfail expects."""


def _refused(lookup_impl, words):
    return pytest.param(lookup_impl, words, marks=pytest.mark.xfail(
        strict=True, raises=Refused, reason=f"Mosaic refuses: {words!r}"))


def _compile_refusable(one_chip, op, shapes, words, **static):
    try:
        return _compile_op(one_chip, op, shapes, **static)
    except Exception as e:  # noqa: BLE001 — re-raised unless the words match
        if words in str(e):
            raise Refused(words) from e
        raise


def _bitsliced_operands(op, m, k, n):
    planes = ((2, n, k // 4), jnp.uint8)
    if op == "lut_gemm_bs_fused":
        return [((m, k), jnp.bfloat16), planes, ((n,), jnp.float32), None]
    return [((m, k), jnp.int8), planes, None]


@pytest.mark.parametrize("lookup_impl,words", [
    _refused("take", BS_TAKE), _refused("onehot", ONEHOT)])
@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("op", ["lut_gemm_bs_fused", "lut_gemm_bitsliced"])
def test_bitsliced_compiles(one_chip, op, m, lookup_impl, words):
    hlo = _compile_refusable(one_chip, op,
                             _bitsliced_operands(op, m, 2816, 1024), words,
                             w_bits=2, a_bits=8, lookup_impl=lookup_impl)
    assert "tpu_custom_call" in hlo


def test_layer_names_reach_the_chips_program(one_chip, monkeypatch):
    """The layer names of ``repro.obs.scope`` in a decode step compiled for
    the chip: every Pallas kernel keeps its own ``kernel_metadata`` beside
    its ``scope``, and with the names off the program is the same
    instruction for instruction (kernel bodies compared without their
    source locations, which name the calling lines)."""
    import contextlib
    import dataclasses
    import re

    from test_obs_scope import canonical_hlo

    from repro import obs
    from repro.configs import get_config
    from repro.core.qplan import get_plan
    from repro.launch import steps as St
    from repro.models import lm

    B, P = 8, 32
    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b"), n_layers=2, d_model=256, n_heads=4,
        n_kv_heads=4, d_ff=704, vocab_size=4096,
        quant=dataclasses.replace(get_plan("w2a16"), backend="pallas"))

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def decode_hlo():
        params = sds(jax.eval_shape(lambda k: lm.quantize_tree(
            lm.init_params(k, cfg), cfg), jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one_chip)
        prefill = St.make_prefill_step(cfg, max_len=P + 4)
        caches = sds(jax.eval_shape(
            lambda p, t: prefill(p, {"tokens": t})[1], params, tokens))
        batch = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                                sharding=one_chip),
                 "pos": jax.ShapeDtypeStruct((B,), jnp.int32,
                                             sharding=one_chip)}
        return jax.jit(St.make_decode_step(cfg)).lower(
            params, caches, batch).compile().as_text()

    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        named = decode_hlo()
        monkeypatch.setattr(obs, "scope",
                            lambda name: contextlib.nullcontext())
        plain = decode_hlo()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    kernels = [line for line in named.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 7                  # one per linear of a layer
    for line in kernels:
        assert re.search(r'frontend_attributes=\{kernel_metadata=\{\},'
                         r'scope="(attn/(qkv|out)|mlp/(up|down))"\}', line)
    assert "scope=" not in plain
    assert canonical_hlo(named) == canonical_hlo(plain)
