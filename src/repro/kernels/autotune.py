"""Offline Pallas tile-size autotuner (quantize-time, never under jit).

Block shapes (bm, bn, bk) are trace-time constants for Pallas, so searching
them must happen OFFLINE. ``tune`` times the registered candidates of a
KernelOp's ``tile_space`` on synthetic operands for one (op, M, K, N, bits,
G) problem and returns the winner; ``quantize_tree`` calls it once per
distinct shape (memoised through a shared ``TileCache``) when the plan's
``tune`` field lists M buckets, and stamps the winners on each packed
leaf's hashable ``tiles`` aux — where ``core.qlinear.tile_for`` looks them
up by static M at trace time. A lookup miss silently falls back to the
kernel's default blocks: the jit'd forward NEVER tunes (patch-raise
tested, like the PR 4 LUT-construction guarantee).

Tiles are aux (static) data, so checkpoints — which persist only array
leaves and restore through a template — would drop them. ``tile_meta`` /
``apply_tile_meta`` round-trip the stamped tiles through the checkpoint
manifest's JSON ``meta`` dict instead.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.obs import metrics as obs_metrics
from . import registry

# ops the tuner can synthesize operands for (the dense serving routes)
TUNABLE_OPS = ("dequant_matmul", "lut_gemm", "lut_gemm_bitsliced",
               "lut_gemm_bs_fused")

# leaf kernel -> op dense_serve actually dispatches for it (bitsliced plans
# route through the fused-prologue op, so its tiles are what tile_for must
# stamp; the two-step op stays registered and directly tunable)
_LEAF_OP = {"lut_gemm_bitsliced": "lut_gemm_bs_fused"}

TileCache = dict  # (op, m, k, n, bits, group_size) -> (bm, bn, bk) | None


def _synth_args(op_name: str, m: int, k: int, n: int, *, bits: int,
                a_bits: Optional[int], group_size: Optional[int]):
    """Synthetic operands + static kwargs reproducing the dense_serve call
    shapes for one problem size. Values are arbitrary — only timing runs."""
    rng = np.random.default_rng(0)
    sc_shape = (n, k // group_size) if group_size else (n,)
    scales = jnp.asarray(rng.random(sc_shape), jnp.float32)
    if op_name == "dequant_matmul":
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        wp = jnp.asarray(rng.integers(0, 256, (n, packing.packed_len(k, bits))),
                         jnp.uint8)
        cb = jnp.arange(2 ** bits, dtype=jnp.float32)
        return (a, wp, cb, scales), dict(bits=bits, group_size=group_size)
    ab = a_bits or 8
    if op_name == "lut_gemm":
        a_idx = jnp.asarray(rng.integers(0, 2 ** ab, (m, k)), jnp.uint8)
        wp = jnp.asarray(rng.integers(0, 256, (n, packing.packed_len(k, bits))),
                         jnp.uint8)
        table = jnp.asarray(rng.standard_normal(2 ** (bits + ab)), jnp.float32)
        return (a_idx, wp, table, scales if group_size else None), \
            dict(w_bits=bits, a_bits=ab, group_size=group_size)
    if op_name == "lut_gemm_bitsliced":
        g = packing.BITPLANE_GROUP
        a = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
        planes = jnp.asarray(rng.integers(0, 2 ** g, (bits, n, k // g)),
                             jnp.uint8)
        return (a, planes, scales if group_size else None), \
            dict(w_bits=bits, a_bits=ab, group_size=group_size)
    if op_name == "lut_gemm_bs_fused":
        g = packing.BITPLANE_GROUP
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        planes = jnp.asarray(rng.integers(0, 2 ** g, (bits, n, k // g)),
                             jnp.uint8)
        return (x, planes, scales, None), \
            dict(w_bits=bits, a_bits=ab, group_size=group_size)
    raise ValueError(f"op {op_name!r} is not tunable; have {TUNABLE_OPS}")


def _time_once(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))                      # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def tune(
    op_name: str,
    m: int,
    k: int,
    n: int,
    *,
    bits: int,
    a_bits: Optional[int] = None,
    group_size: Optional[int] = None,
    backend: str = "auto",
    cache: Optional[TileCache] = None,
    iters: int = 2,
) -> Optional[tuple[int, int, int]]:
    """Search the op's tile space for one problem; returns the fastest
    (bm, bn, bk) or None when blocks are irrelevant ('ref' backend / no
    Pallas impl / no tile space). Memoised through ``cache`` so repeated
    layer shapes tune once. Probe traces run under an isolated metrics
    scope — the tuner's dispatches never leak into serving gates."""
    key = (op_name, int(m), int(k), int(n), int(bits),
           int(group_size or 0))
    if cache is not None and key in cache:
        return cache[key]
    op = registry.get(op_name)
    b = registry.resolve_backend(backend)
    result: Optional[tuple[int, int, int]] = None
    if b != "ref" and op.pallas is not None and op.tile_space is not None:
        args, static = _synth_args(op_name, m, k, n, bits=bits,
                                   a_bits=a_bits, group_size=group_size)
        with obs_metrics.scoped(isolate=True):
            best_t = None
            for blk in op.tile_space(m, k, n, static):
                fn = jax.jit(lambda *xs, _blk=blk: registry.dispatch(
                    op_name, *xs, backend=b, block=_blk, **static))
                t = _time_once(fn, args, iters)
                if best_t is None or t < best_t:
                    best_t, result = t, tuple(int(v) for v in blk)
    if cache is not None:
        cache[key] = result
    return result


def tune_leaf_tiles(
    qw_kernel: str,
    k_padded: int,
    n: int,
    *,
    bits: int,
    a_bits: Optional[int],
    group_size: Optional[int],
    m_buckets: tuple,
    backend: str = "auto",
    cache: Optional[TileCache] = None,
) -> tuple:
    """Tune every requested M bucket for one leaf's problem shape; returns
    the ``tiles`` aux tuple ((m, bm, bn, bk), ...) sorted by m. The leaf's
    kernel name maps through ``_LEAF_OP`` first, so bitsliced leaves tune
    the fused-prologue op dense_serve will actually dispatch."""
    if qw_kernel not in TUNABLE_OPS:
        return ()
    op_name = _LEAF_OP.get(qw_kernel, qw_kernel)
    tiles = []
    for m in sorted({int(v) for v in m_buckets}):
        blk = tune(op_name, m, k_padded, n, bits=bits, a_bits=a_bits,
                   group_size=group_size, backend=backend, cache=cache)
        if blk is not None:
            tiles.append((m, *blk))
    return tuple(tiles)


# --------------------------------------------------------------------------- #
# Checkpoint round-trip: tiles live in AUX, so they ride the manifest meta
# --------------------------------------------------------------------------- #

def tile_meta(tree: Any) -> dict:
    """Collect every packed leaf's stamped tiles as a JSON-able dict
    {path: [[m, bm, bn, bk], ...]} for checkpoint.save_checkpoint(meta=...)."""
    from repro.core.qlinear import QuantizedWeight
    out = {}
    leaves = jax.tree_util.tree_leaves_with_path(
        tree, is_leaf=lambda x: isinstance(x, QuantizedWeight))
    for path, leaf in leaves:
        if isinstance(leaf, QuantizedWeight) and leaf.tiles:
            out[jax.tree_util.keystr(path)] = [list(t) for t in leaf.tiles]
    return out


def apply_tile_meta(tree: Any, meta: dict) -> Any:
    """Re-stamp saved tiles onto a restored tree/template (inverse of
    ``tile_meta``); paths absent from ``meta`` keep their current tiles."""
    import dataclasses
    from repro.core.qlinear import QuantizedWeight
    if not meta:
        return tree

    def visit(path, leaf):
        if isinstance(leaf, QuantizedWeight):
            saved = meta.get(jax.tree_util.keystr(path))
            if saved is not None:
                return dataclasses.replace(
                    leaf, tiles=tuple(tuple(int(v) for v in t) for t in saved))
        return leaf

    return jax.tree_util.tree_map_with_path(
        visit, tree, is_leaf=lambda x: isinstance(x, QuantizedWeight))
