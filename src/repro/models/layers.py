"""Transformer building blocks, pure JAX, quantization-aware.

Every dense projection goes through `dense()` which dispatches between:
  * plain bf16 matmul,
  * QAT (LSQ fake-quant, paper Tab. 1 methodology),
  * packed serving (QuantizedWeight leaf -> codebook dequant path; the Pallas
    kernels implement the same math tile-wise on TPU, the jnp formulation here
    is what GSPMD shards in the dry-run).

Attention is flash-style (chunked online softmax, lax.scan over KV chunks,
lax.map over query chunks) so the 32k/500k cells compile with bounded VMEM-
scale buffers instead of S^2 score matrices. Supports causal, sliding-window,
cross (encoder-decoder), GQA/MQA, RoPE and M-RoPE, ring-buffer KV caches for
local layers.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro import obs
from repro.core import calibrate, quant
from repro.core.qlinear import (QuantPolicy, QuantizedWeight, dense_serve,
                                dequant_weight)
from repro.core.qplan import plan_backend
from repro.dist.sharding import shard


# --------------------------------------------------------------------------- #
# Dense dispatch (plain | qat | packed-serve)
# --------------------------------------------------------------------------- #
#
# ``policy`` everywhere below is either a single QuantPolicy (legacy) or a
# qplan.QuantPlan (ordered tag -> policy table); both expose ``policy_for``.

def dense_init(key, din: int, dout: int, *, bias: bool = False, tag: str = "",
               policy, mode: str, dtype=jnp.float32) -> dict:
    """mode 'qat' attaches LSQ step parameters where the policy applies."""
    w = jax.random.normal(key, (din, dout), dtype) * (din ** -0.5)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((dout,), dtype)
    lp = policy.policy_for(tag)
    if mode == "qat" and lp is not None:
        p["w_step"] = quant.lsq_init_step(w, lp.w_bits, lp.signed).astype(dtype)
        if lp.a_bits is not None:
            p["a_step"] = jnp.asarray(0.05, dtype)
    return p


def dense(p: dict, x: jax.Array, *, tag: str = "", policy,
          mode: str = "plain") -> jax.Array:
    """x: (..., in) -> (..., out).

    Packed serving leaves ({"qw": QuantizedWeight}) dispatch on the leaf's
    plan: ``qw.kernel`` set routes through the kernels/registry KernelOp
    table (dequant_matmul for w{b}a16, lut_gemm or lut_gemm_bitsliced with
    dynamic activation quantization for w{b}a{b}) on the plan's backend;
    ``qw.kernel`` None keeps the legacy dequant-einsum formulation
    bit-for-bit (the GSPMD-shardable dry-run form).
    """
    calibrate.observe(tag, x)   # no-op outside a calibration context
    if "qw" in p:  # packed serving leaf
        qw: QuantizedWeight = p["qw"]
        if qw.kernel is not None:  # planned: kernel-backed hot path
            return dense_serve(qw, x, bias=p.get("b"),
                               backend=plan_backend(policy))
        w = dequant_weight(qw).astype(x.dtype)        # codebook LUT dequant
        y = x @ w
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y
    w = p["w"]
    if mode == "qat" and "w_step" in p:
        lp = policy.policy_for(tag) or (policy if isinstance(policy, QuantPolicy)
                                        else None)
        if lp is not None and lp.w_bits is not None:
            w = quant.lsq_fake_quant(w, p["w_step"], lp.w_bits, lp.signed)
            if "a_step" in p and lp.a_bits is not None:
                x = quant.lsq_fake_quant(x, p["a_step"], lp.a_bits, lp.signed)
    y = x @ w.astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #

def norm_init(d: int, kind: str, dtype=jnp.float32) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p: dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    with obs.scope("norm"):
        xf = x.astype(jnp.float32)
        if kind == "layernorm":
            mu = xf.mean(-1, keepdims=True)
            xf = xf - mu
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
        if "bias" in p:
            y = y + p["bias"].astype(jnp.float32)
        return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# RoPE / M-RoPE
# --------------------------------------------------------------------------- #

def _rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2)))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Optional[tuple] = None) -> jax.Array:
    """x: (B, S, N, hd). positions: (B, S) or (B, S, 3) for M-RoPE.

    M-RoPE (qwen2-vl): the hd/2 rotary frequency channels are split into
    (t, h, w) sections; each section takes its angle from the corresponding
    position coordinate."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                        # (hd/2,)
    if mrope_sections is None:
        if positions.ndim == 3:
            positions = positions[..., 0]
        ang = positions[..., None].astype(jnp.float32) * freqs   # (B, S, hd/2)
    else:
        assert positions.ndim == 3 and sum(mrope_sections) == hd // 2
        parts, off = [], 0
        for i, sec in enumerate(mrope_sections):
            parts.append(positions[..., i, None].astype(jnp.float32)
                         * freqs[off:off + sec])
            off += sec
        ang = jnp.concatenate(parts, axis=-1)             # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Flash-style attention (chunked online softmax)
# --------------------------------------------------------------------------- #

def _attn_chunk_sizes(sq: int, sk: int) -> tuple[int, int]:
    qc = min(1024, sq)
    kc = min(1024, sk)
    while sq % qc:
        qc //= 2
    while sk % kc:
        kc //= 2
    return max(qc, 1), max(kc, 1)


def flash_attention(
    q: jax.Array,            # (B, Sq, KV, G, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,            # (B, Sk, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,                             # scalar or (B,) per-row offset
    segments: Optional[jax.Array] = None,   # (B, S) packed-sequence ids
) -> jax.Array:
    """Memory-bounded attention: lax.map over query chunks, lax.scan over key
    chunks, online max/denominator. Returns (B, Sq, KV, G, hd).

    q_offset: absolute position of query row 0 — a scalar shared by the
    batch, or a (B,) vector when rows sit at different offsets (the batched
    multi-request prefill chunk). The scalar path keeps its original
    (qc, kc) mask shapes bit-for-bit.

    segments: sequence-packing ids — attention is masked to seg_q == seg_k
    so multiple documents share one row without cross-attending."""
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    scale = hd ** -0.5
    qc, kc = _attn_chunk_sizes(Sq, Sk)
    nq, nk = Sq // qc, Sk // kc
    qr = q.reshape(B, nq, qc, KV, G, hd)
    neg = jnp.asarray(-1e30, jnp.float32)
    per_row = jnp.ndim(q_offset) == 1

    def q_block(args):
        qi, qb = args                                    # qb: (B, qc, KV, G, hd)
        if per_row:
            qpos = q_offset[:, None] + qi * qc + jnp.arange(qc)  # (B, qc)
        else:
            qpos = q_offset + qi * qc + jnp.arange(qc)           # (qc,)
        seg_q = (jax.lax.dynamic_slice_in_dim(segments, qi * qc, qc, 1)
                 if segments is not None else None)

        def k_step(carry, ki):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, ki * kc, kc, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, ki * kc, kc, 1)
            s = jnp.einsum("bqegh,bseh->begqs", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale   # (B,KV,G,qc,kc)
            kpos = ki * kc + jnp.arange(kc)
            if per_row:
                mask = jnp.ones((B, qc, kc), bool)
                if causal:
                    mask &= qpos[:, :, None] >= kpos[None, None, :]
                if window is not None:
                    mask &= (qpos[:, :, None] - kpos[None, None, :]) < window
                s = jnp.where(mask[:, None, None], s, neg)
            else:
                mask = jnp.ones((qc, kc), bool)
                if causal:
                    mask &= qpos[:, None] >= kpos[None, :]
                if window is not None:
                    mask &= (qpos[:, None] - kpos[None, :]) < window
                s = jnp.where(mask, s, neg)
            if seg_q is not None:
                seg_k = jax.lax.dynamic_slice_in_dim(segments, ki * kc, kc, 1)
                smask = seg_q[:, :, None] == seg_k[:, None, :]   # (B,qc,kc)
                s = jnp.where(smask[:, None, None], s, neg)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("begqs,bseh->begqh", p, vb.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, qc), neg, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qc, hd), jnp.float32)
        # checkpoint the k-step: backward recomputes the (qc, kc) score tile
        # per chunk instead of saving an (nk, ..., qc, kc) stack — this is
        # what makes the backward flash-shaped (O(S) memory, not O(S^2)).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(k_step),
                                      (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,KV,G,qc,hd)
        return out.transpose(0, 3, 1, 2, 4)               # (B,qc,KV,G,hd)

    if nq == 1:
        out = q_block((jnp.asarray(0), qr[:, 0]))[:, None]
    else:
        out = jax.lax.map(q_block, (jnp.arange(nq), qr.transpose(1, 0, 2, 3, 4, 5)))
        out = out.transpose(1, 0, 2, 3, 4, 5)              # (B,nq,qc,KV,G,hd)
    return out.reshape(B, Sq, KV, G, hd).astype(q.dtype)


def decode_attention(
    q: jax.Array,            # (B, 1, KV, G, hd)
    k_cache: jax.Array,      # (B, S, KV, hd)
    v_cache: jax.Array,      # (B, S, KV, hd)
    valid: jax.Array,        # (B, S) bool
) -> jax.Array:
    """Single-query attention over a (possibly ring) cache."""
    hd = q.shape[-1]
    s = jnp.einsum("bqegh,bseh->begqs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * hd ** -0.5
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("begqs,bseh->bqegh", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def masked_attention(
    q: jax.Array,            # (B, Sq, KV, G, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,            # (B, Sk, KV, hd)
    valid: jax.Array,        # (B, Sq, Sk) bool, per-query key mask
) -> jax.Array:
    """Dense attention under an arbitrary per-query mask — the ring-paged
    local path, where key rows are a ring view + the in-flight chunk and the
    mask encodes both the ring recency window and in-chunk causality. Key
    count is O(window), so the dense (Sq, Sk) score tile stays small by
    construction."""
    hd = q.shape[-1]
    s = jnp.einsum("bqegh,bseh->begqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    s = jnp.where(valid[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("begqs,bseh->bqegh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _scatter_pool_rows(pool: jax.Array, new: jax.Array, blk: jax.Array,
                       offs: jax.Array) -> jax.Array:
    """Scatter per-token rows ``new`` (B, S, ...) into a paged pool at
    (block, offset) coordinates ``blk`` / ``offs`` (both (B, S))."""
    B, S = blk.shape
    return pool.at[blk.reshape(-1), offs.reshape(-1)].set(
        new.reshape(B * S, *new.shape[2:]).astype(pool.dtype))


# --------------------------------------------------------------------------- #
# Attention layer (self / cross, cached / uncached)
# --------------------------------------------------------------------------- #

def attn_init(key, cfg, *, mode: str, dtype=jnp.float32, cross: bool = False) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    pol = cfg.quant
    p = {
        "wq": dense_init(ks[0], D, H * hd, bias=cfg.qkv_bias, tag="attn.wq",
                         policy=pol, mode=mode, dtype=dtype),
        "wk": dense_init(ks[1], D, KV * hd, bias=cfg.qkv_bias, tag="attn.wk",
                         policy=pol, mode=mode, dtype=dtype),
        "wv": dense_init(ks[2], D, KV * hd, bias=cfg.qkv_bias, tag="attn.wv",
                         policy=pol, mode=mode, dtype=dtype),
        "wo": dense_init(ks[3], H * hd, D, bias=False, tag="attn.wo",
                         policy=pol, mode=mode, dtype=dtype),
    }
    return p


def _ring_update(cache: jax.Array, new: jax.Array, pos: jax.Array,
                 window: int) -> jax.Array:
    """cache (B, W, KV, ...), new (B, 1, KV, ...), pos (B,) absolute."""
    slot = pos % window

    def upd(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s,) + (0,) * (c.ndim - 1))

    return jax.vmap(upd)(cache, new, slot)


# Largest row of a layer's cache, in bytes, whose one new position is
# written by a select over every position. K, V and their scales count
# together, so one layer takes one route for all its arrays. Below it the
# select reads and writes the whole row at HBM speed; above it the vmapped
# ``dynamic_update_slice`` stays, which XLA lowers to a scatter that the
# TPU compiler runs as a loop over rows, at a fixed cost a row whatever the
# row's length. The two cost the same at about 5 MB a row on a TPU v5e
# (PERF.md, Findings).
KV_SELECT_MAX_ROW_BYTES = 4 * 2**20


def _cache_update(cache: dict, new: dict, pos: jax.Array) -> dict:
    """Write each sequence's new positions into a layer's cache arrays.

    For each name ``n`` of ``new`` (K, V and, for a quantised cache, their
    scales): ``cache[n]`` (B, S, KV, ...), ``new[n]`` (B, T, KV, ...). Row
    b's T new positions start at ``pos[b]``, taken as
    ``lax.dynamic_update_slice`` takes it: counted from the end if
    negative, then clamped to [0, S - T]. Records the route taken in
    ``kv_cache_write_total{route=...}`` at trace time.
    """
    names = list(new)
    row_bytes = sum(math.prod(cache[n].shape[1:]) * cache[n].dtype.itemsize
                    for n in names)
    select = (all(new[n].shape[1] == 1 for n in names)
              and row_bytes <= KV_SELECT_MAX_ROW_BYTES)
    obs.metrics.inc("kv_cache_write_total",
                    route="select" if select else "rows")
    if select:
        S = cache[names[0]].shape[1]
        start = jnp.clip(jnp.where(pos < 0, pos + S, pos), 0, S - 1)
        hit = jnp.arange(S)[None, :] == start[:, None]

        def upd(c, x):
            return jnp.where(hit.reshape(hit.shape + (1,) * (c.ndim - 2)), x, c)
        return {n: upd(cache[n], new[n]) for n in names}

    def upd_row(c, x, s):
        return jax.lax.dynamic_update_slice(c, x, (s,) + (0,) * (c.ndim - 1))

    return {n: jax.vmap(upd_row)(cache[n], new[n], pos) for n in names}


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """bf16 (B, S, KV, hd) -> (int8 codes, per-(token, head) scales).
    The paper's theme applied to the decode cache: 2x fewer HBM bytes on the
    decode-dominating cache read, absorbed by a per-head codebook scale."""
    sc = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
                     / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / sc[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, sc


def dequantize_kv(q: jax.Array, sc: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * sc[..., None]


def quantize_kv4(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """4-bit packed cache: the paper's sub-byte packing machinery (pack/
    unpack + uniform codebook + per-(token, head) scale) on K/V — 4x fewer
    cache bytes than bf16. Codes packed 2-per-byte along head_dim."""
    from repro.core import packing
    sc = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
                     / 7.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / sc[..., None]), -8, 7)
    idx = (q + 8).astype(jnp.uint8)
    return packing.pack(idx, 4), sc


def dequantize_kv4(packed: jax.Array, sc: jax.Array) -> jax.Array:
    from repro.core import packing
    idx = packing.unpack(packed, 4).astype(jnp.float32)
    return (idx - 8.0) * sc[..., None]


KV_QUANT = {"int8": (quantize_kv, dequantize_kv),
            "int4": (quantize_kv4, dequantize_kv4)}


def attn_apply(
    p: dict,
    x: jax.Array,                       # (B, S, D)
    *,
    cfg,
    layer_type: str = "global",         # "global" | "local"
    mode: str = "plain",
    positions: Optional[jax.Array] = None,   # (B,S) or (B,S,3)
    enc_out: Optional[jax.Array] = None,     # cross-attention memory
    cache: Optional[dict] = None,            # {"k","v"} (+ ring) or {"xk","xv"}
    pos: Optional[jax.Array] = None,         # (B,) decode position
    segments: Optional[jax.Array] = None,    # (B,S) packed-sequence ids
    block_tables: Optional[jax.Array] = None,  # (B, nb) paged-cache tables
    ring_tables: Optional[jax.Array] = None,   # (B, ring_len) local-layer ring
    kv_splits: Optional[int] = None,           # static flash-decode split count
) -> tuple[jax.Array, Optional[dict]]:
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    pol = cfg.quant
    cross = enc_out is not None or (cache is not None and "xk" in cache)
    window = cfg.window if layer_type == "local" else None

    with obs.scope("attn/qkv"):
        q = dense(p["wq"], x, tag="attn.wq", policy=pol, mode=mode)
        q = q.reshape(B, S, KV, G, hd)
        q = shard(q, "batch", "seq", "kv_heads_act", None, None)

    new_cache = None
    if cross:
        if cache is not None and "xk" in cache:
            k, v = cache["xk"], cache["xv"]
        else:
            with obs.scope("attn/qkv"):
                k = dense(p["wk"], enc_out, tag="attn.wk", policy=pol,
                          mode=mode)
                v = dense(p["wv"], enc_out, tag="attn.wv", policy=pol,
                          mode=mode)
                k = k.reshape(B, -1, KV, hd)
                v = v.reshape(B, -1, KV, hd)
            new_cache = {"xk": k, "xv": v}
        with obs.scope("attn/core"):
            out = flash_attention(q, k, v, causal=False)
    else:
        with obs.scope("attn/qkv"):
            k = dense(p["wk"], x, tag="attn.wk", policy=pol, mode=mode).reshape(B, S, KV, hd)
            v = dense(p["wv"], x, tag="attn.wv", policy=pol, mode=mode).reshape(B, S, KV, hd)
            if cfg.pos_embed == "rope":
                if positions is None:
                    # (1, S) when batch-independent: keeps cos/sin tables
                    # tiny instead of materializing (B, S, hd) angle tensors.
                    positions = (jnp.arange(S)[None, :] if pos is None
                                 else pos[:, None] + jnp.arange(S)[None, :])
                q = apply_rope(q.reshape(B, S, H, hd), positions,
                               cfg.rope_theta,
                               cfg.mrope_sections).reshape(B, S, KV, G, hd)
                k = apply_rope(k, positions, cfg.rope_theta,
                               cfg.mrope_sections)
        if cache is not None and (block_tables is not None
                                  or ring_tables is not None):
            # Paged cache (serving engine): the layer cache is a shared block
            # pool (n_blocks, bs_tok, KV, ...) and block_tables maps each
            # row's logical block j to a physical block. Gather the slot's
            # blocks into a dense (B, S_view) view, update rows
            # [pos, pos + S), attend with the SAME masked math as the dense
            # path (bit-identical on equal view lengths), then scatter only
            # the written rows back into the pool.
            bs_tok = cache["k"].shape[1]
            int8_cache = cfg.kv_cache_dtype in KV_QUANT and "k_sc" in cache
            if int8_cache:
                qf, dqf = KV_QUANT[cfg.kv_cache_dtype]
                with obs.scope("attn/kv_write"):
                    k, k_sc = qf(k)
                    v, v_sc = qf(v)

            if layer_type == "local" and ring_tables is not None:
                # Ring-paged local layer: the pool holds only ring_len blocks
                # per slot (absolute row t lives at ring row t mod R), so
                # memory per request is O(window), flat in context length.
                # Attend over [pre-write ring view ++ in-flight chunk]: the
                # ring view carries rows <= pos-1 (each ring row's absolute
                # position recovered from pos and its ring index), the chunk
                # adds rows [pos, pos+S) causally — then scatter the chunk
                # into its ring slots. Correctness needs R >= window + span
                # - 1 (span = max chunk/spec-verify advance): stale or pad
                # rows alias a full R below their write position, which the
                # recency mask then rejects.
                ring_len = ring_tables.shape[1]
                R = ring_len * bs_tok

                def rgather(pool):
                    g = pool[ring_tables]                # (B, ring_len, bs,.)
                    return g.reshape(B, R, *pool.shape[2:])

                with obs.scope("attn/core"):
                    if int8_cache:
                        kd = jnp.concatenate(
                            [dqf(rgather(cache["k"]), rgather(cache["k_sc"])),
                             dqf(k, k_sc)], axis=1)
                        vd = jnp.concatenate(
                            [dqf(rgather(cache["v"]), rgather(cache["v_sc"])),
                             dqf(v, v_sc)], axis=1)
                    else:
                        kd = jnp.concatenate(
                            [rgather(cache["k"]), k.astype(cache["k"].dtype)],
                            axis=1)
                        vd = jnp.concatenate(
                            [rgather(cache["v"]), v.astype(cache["v"].dtype)],
                            axis=1)

                    last = pos - 1                       # newest ring row
                    ridx = jnp.arange(R)[None, :]
                    qabs = last[:, None] - jnp.mod(last[:, None] - ridx, R)
                    t = pos[:, None] + jnp.arange(S)[None, :]      # (B, S)
                    valid_ring = ((qabs[:, None, :] >= 0)
                                  & (qabs[:, None, :] > t[:, :, None] - window))
                    sidx = jnp.arange(S)
                    valid_cur = ((sidx[None, None, :] <= sidx[None, :, None])
                                 & (sidx[None, :, None] - sidx[None, None, :]
                                    < window))
                    valid = jnp.concatenate(
                        [valid_ring, jnp.broadcast_to(valid_cur, (B, S, S))],
                        axis=2)
                    out = masked_attention(q, kd, vd, valid)

                with obs.scope("attn/kv_write"):
                    rows = pos[:, None] + jnp.arange(S)[None, :]
                    blk = jnp.take_along_axis(
                        ring_tables, (rows // bs_tok) % ring_len, axis=1)
                    offs = rows % bs_tok
                    new_cache = {
                        "k": _scatter_pool_rows(cache["k"], k, blk, offs),
                        "v": _scatter_pool_rows(cache["v"], v, blk, offs)}
                    if int8_cache:
                        new_cache["k_sc"] = _scatter_pool_rows(
                            cache["k_sc"], k_sc, blk, offs)
                        new_cache["v_sc"] = _scatter_pool_rows(
                            cache["v_sc"], v_sc, blk, offs)
                return _attn_out(p, out, cfg=cfg, mode=mode), new_cache

            nb = block_tables.shape[1]
            S_view = nb * bs_tok
            with obs.scope("attn/kv_write"):
                rows = pos[:, None] + jnp.arange(S)[None, :]         # (B, S)
                blk = jnp.take_along_axis(
                    block_tables, jnp.minimum(rows // bs_tok, nb - 1), axis=1)
                offs = rows % bs_tok

            if kv_splits is not None and int(kv_splits) > 1 and S == 1:
                # Flash-decoding split-KV decode: scatter the new row FIRST,
                # then reduce the block table in kv_splits chunks — the
                # chunk axis is a tensor dim (one blocked masked-softmax
                # pass yielding per-chunk unnormalized partials), merged
                # exactly by merge_splitkv_partials. Scattering before
                # attending skips the single-pass path's full-width
                # gathered-view update copy (_cache_update), and the f32
                # score/value contractions accumulate straight off the pool
                # dtype — which is what makes long-context decode faster
                # than single-pass.
                with obs.scope("attn/kv_write"):
                    new_cache = {
                        "k": _scatter_pool_rows(cache["k"], k, blk, offs),
                        "v": _scatter_pool_rows(cache["v"], v, blk, offs)}
                    if int8_cache:
                        new_cache["k_sc"] = _scatter_pool_rows(
                            cache["k_sc"], k_sc, blk, offs)
                        new_cache["v_sc"] = _scatter_pool_rows(
                            cache["v_sc"], v_sc, blk, offs)
                from repro.kernels.paged_attention import (
                    merge_splitkv_partials)
                ns = min(int(kv_splits), nb)
                nbc = -(-nb // ns)

                def cgather(pool):                       # (B, ns, nbc*bs, .)
                    g = pool[tblp]
                    return g.reshape(B, ns, nbc * bs_tok, *pool.shape[2:])

                with obs.scope("attn/core"):
                    tblp = jnp.pad(block_tables, ((0, 0), (0, ns * nbc - nb)))
                    qf32 = q[:, 0].astype(jnp.float32)   # (B, KV, G, hd)
                    scale = hd ** -0.5
                    if int8_cache:
                        kd = dqf(cgather(new_cache["k"]),
                                 cgather(new_cache["k_sc"]))
                        vd = dqf(cgather(new_cache["v"]),
                                 cgather(new_cache["v_sc"]))
                    else:
                        # no f32 materialization of the view: the
                        # contractions below accumulate in f32 straight off
                        # the pool dtype
                        kd, vd = cgather(new_cache["k"]), cgather(new_cache["v"])
                    idx = jnp.arange(ns * nbc * bs_tok).reshape(ns, nbc * bs_tok)
                    cvalid = idx[None] <= pos[:, None, None]
                    if window is not None:
                        cvalid &= idx[None] > pos[:, None, None] - window
                    s = jnp.einsum("begh,bnseh->bnegs", qf32, kd,
                                   preferred_element_type=jnp.float32) * scale
                    s = jnp.where(cvalid[:, :, None, None, :], s, -1e30)
                    m_c = s.max(-1)                      # (B, ns, KV, G)
                    pr = jnp.exp(s - m_c[..., None])
                    acc = jnp.einsum("bnegs,bnseh->bnegh", pr, vd,
                                     preferred_element_type=jnp.float32)
                    out = merge_splitkv_partials(acc, m_c, pr.sum(-1))
                    out = out[:, None].astype(q.dtype)   # (B, 1, KV, G, hd)
                return _attn_out(p, out, cfg=cfg, mode=mode), new_cache

            def gather(pool):
                # the read of the cache that attention pays for
                with obs.scope("attn/core"):
                    g = pool[block_tables]               # (B, nb, bs_tok, ..)
                    return g.reshape(B, S_view, *pool.shape[2:])

            with obs.scope("attn/kv_write"):
                new = {"k": k, "v": v}
                if int8_cache:
                    new.update(k_sc=k_sc, v_sc=v_sc)
                upd = _cache_update({n: gather(cache[n]) for n in new}, new,
                                    pos)
                kc, vc = upd["k"], upd["v"]
                if int8_cache:
                    ksc, vsc = upd["k_sc"], upd["v_sc"]
            with obs.scope("attn/core"):
                if int8_cache:
                    kd, vd = dqf(kc, ksc), dqf(vc, vsc)
                else:
                    kd, vd = kc, vc

                if S == 1:                               # decode step
                    valid = jnp.arange(S_view)[None, :] <= pos[:, None]
                    if window is not None:  # local layer: paged by absolute
                        # position, masked to the window (not ring-folded)
                        valid &= (jnp.arange(S_view)[None, :]
                                  > pos[:, None] - window)
                    out = decode_attention(q, kd, vd, valid)
                else:                                    # chunked prefill
                    # one or more request rows, each starting at its own
                    # pos; the causal mask from the per-row q_offset also
                    # blanks the not-yet-written pool tail (exact zeros after
                    # softmax, so garbage rows are inert)
                    out = flash_attention(q, kd, vd, causal=True,
                                          window=window, q_offset=pos)

            with obs.scope("attn/kv_write"):
                new_cache = {
                    "k": _scatter_pool_rows(cache["k"], k, blk, offs),
                    "v": _scatter_pool_rows(cache["v"], v, blk, offs)}
                if int8_cache:
                    new_cache["k_sc"] = _scatter_pool_rows(cache["k_sc"], k_sc,
                                                           blk, offs)
                    new_cache["v_sc"] = _scatter_pool_rows(cache["v_sc"], v_sc,
                                                           blk, offs)
        elif cache is not None:               # dense slot cache, decode S == 1
            int8_cache = cfg.kv_cache_dtype in KV_QUANT and "k_sc" in cache
            with obs.scope("attn/kv_write"):
                if int8_cache:
                    qf, dqf = KV_QUANT[cfg.kv_cache_dtype]
                    k, k_sc = qf(k)
                    v, v_sc = qf(v)
                if window is not None:            # ring buffer cache
                    kc = _ring_update(cache["k"], k, pos, window)
                    vc = _ring_update(cache["v"], v, pos, window)
                    if int8_cache:
                        ksc = _ring_update(cache["k_sc"], k_sc, pos, window)
                        vsc = _ring_update(cache["v_sc"], v_sc, pos, window)
                    W = kc.shape[1]
                    with obs.scope("attn/core"):
                        filled = jnp.minimum(pos + 1, W)
                        valid = jnp.arange(W)[None, :] < filled[:, None]
                else:
                    new = {"k": k, "v": v}
                    if int8_cache:
                        new.update(k_sc=k_sc, v_sc=v_sc)
                    upd = _cache_update(cache, new, pos)
                    kc, vc = upd["k"], upd["v"]
                    if int8_cache:
                        ksc, vsc = upd["k_sc"], upd["v_sc"]
                    Sc = kc.shape[1]
                    with obs.scope("attn/core"):
                        valid = jnp.arange(Sc)[None, :] <= pos[:, None]
                kc = shard(kc, "batch", "kv_seq", "kv_heads_act", None)
                vc = shard(vc, "batch", "kv_seq", "kv_heads_act", None)
            with obs.scope("attn/core"):
                if int8_cache:
                    new_cache = {"k": kc, "v": vc, "k_sc": ksc, "v_sc": vsc}
                    out = decode_attention(q, dqf(kc, ksc), dqf(vc, vsc),
                                           valid)
                else:
                    new_cache = {"k": kc, "v": vc}
                    out = decode_attention(q, kc, vc, valid)
        else:                                 # train / prefill
            with obs.scope("attn/kv_write"):  # the K/V the prefill collects
                k = shard(k, "batch", "kv_seq", "kv_heads_act", None)
                v = shard(v, "batch", "kv_seq", "kv_heads_act", None)
            with obs.scope("attn/core"):
                rep = cfg.kv_repeat
                if rep > 1 and H % (KV * rep) == 0:
                    # replicate kv heads to the TP degree: every model shard
                    # gets its own q/kv head slice -> attention is TP-local
                    # (no per-layer kv all-gather). Cache keeps the
                    # unreplicated GQA kv.
                    ka = jnp.repeat(k, rep, axis=2)
                    va = jnp.repeat(v, rep, axis=2)
                    ka = shard(ka, "batch", "kv_seq", "kv_heads_act", None)
                    va = shard(va, "batch", "kv_seq", "kv_heads_act", None)
                    qa = q.reshape(B, S, KV * rep, H // (KV * rep), hd)
                    qa = shard(qa, "batch", "seq", "kv_heads_act", None, None)
                    out = flash_attention(qa, ka, va, causal=True,
                                          window=window, segments=segments)
                    out = out.reshape(B, S, KV, G, hd)
                else:
                    out = flash_attention(q, k, v, causal=True, window=window,
                                          segments=segments)
            new_cache = {"k": k, "v": v}

    return _attn_out(p, out, cfg=cfg, mode=mode), new_cache


def _attn_out(p: dict, out: jax.Array, *, cfg, mode: str) -> jax.Array:
    """Attention output (B, S, KV, G, hd) -> the ``wo`` projection (B, S, D)."""
    B, S = out.shape[:2]
    with obs.scope("attn/out"):
        out = out.reshape(B, S, cfg.n_heads * cfg.hd)
        out = shard(out, "batch", "seq", "heads_act")
        y = dense(p["wo"], out, tag="attn.wo", policy=cfg.quant, mode=mode)
        return checkpoint_name(shard(y, "batch", "seq_sp", "embed_act"),
                               "block_out")


# --------------------------------------------------------------------------- #
# MLP (swiglu / geglu / gelu)
# --------------------------------------------------------------------------- #

def mlp_init(key, cfg, *, d_ff: Optional[int] = None, mode: str,
             dtype=jnp.float32, tag: str = "mlp") -> dict:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    pol = cfg.quant
    p = {"w_up": dense_init(ks[1], D, F, tag=f"{tag}.w_up", policy=pol,
                            mode=mode, dtype=dtype),
         "w_down": dense_init(ks[2], F, D, tag=f"{tag}.w_down", policy=pol,
                              mode=mode, dtype=dtype)}
    if cfg.mlp in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[0], D, F, tag=f"{tag}.w_gate", policy=pol,
                                 mode=mode, dtype=dtype)
    return p


def mlp_apply(p: dict, x: jax.Array, *, cfg, mode: str = "plain",
              tag: str = "mlp") -> jax.Array:
    pol = cfg.quant
    with obs.scope("mlp/up"):
        up = dense(p["w_up"], x, tag=f"{tag}.w_up", policy=pol, mode=mode)
        if "w_gate" in p:
            g = dense(p["w_gate"], x, tag=f"{tag}.w_gate", policy=pol,
                      mode=mode)
            act = jax.nn.silu(g) if cfg.mlp == "swiglu" else jax.nn.gelu(g)
            h = act * up
        else:
            h = jax.nn.gelu(up)
        h = shard(h, "batch", "seq", "mlp_act")
    with obs.scope("mlp/down"):
        y = dense(p["w_down"], h, tag=f"{tag}.w_down", policy=pol, mode=mode)
        return checkpoint_name(shard(y, "batch", "seq_sp", "embed_act"),
                               "block_out")


# --------------------------------------------------------------------------- #
# MoE (GShard-style dense dispatch; EP over 'experts' logical axis)
# --------------------------------------------------------------------------- #

def moe_init(key, cfg, *, mode: str, dtype=jnp.float32) -> dict:
    moe = cfg.moe
    D, F, E = cfg.d_model, moe.d_ff_expert, moe.n_experts
    ks = jax.random.split(key, 5)
    pol = cfg.quant
    p = {
        "w_router": jax.random.normal(ks[0], (D, E), jnp.float32) * (D ** -0.5),
        "we_gate": jax.random.normal(ks[1], (E, D, F), dtype) * (D ** -0.5),
        "we_up": jax.random.normal(ks[2], (E, D, F), dtype) * (D ** -0.5),
        "we_down": jax.random.normal(ks[3], (E, F, D), dtype) * (F ** -0.5),
    }
    lp = pol.policy_for("moe.experts")
    if mode == "qat" and lp is not None:
        for n in ("we_gate", "we_up", "we_down"):
            p[n + "_step"] = quant.lsq_init_step(p[n], lp.w_bits, lp.signed).astype(dtype)
    if moe.n_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=moe.n_shared * F, mode=mode,
                               dtype=dtype, tag="moe.shared")
    return p


def _expert_w(p: dict, name: str, *, pol, mode: str) -> jax.Array:
    w = p[name]
    if isinstance(w, QuantizedWeight):
        return dequant_weight(w)                       # (E, D, F) f32
    if mode == "qat" and name + "_step" in p:
        lp = pol.policy_for("moe.experts") or (pol if isinstance(pol, QuantPolicy)
                                               else None)
        if lp is not None and lp.w_bits is not None:
            w = quant.lsq_fake_quant(w, p[name + "_step"], lp.w_bits, lp.signed)
    return w


def _expert_matmul(qw: QuantizedWeight, x: jax.Array, backend: str) -> jax.Array:
    """Planned expert projection: x (E, M, D_in) -> (E, M, D_out) f32 through
    the grouped packed-weight kernels. Mirrors the K padding
    quantize_expert_weight applied.

    w{b}a16 plans contract through ``expert_dequant_matmul``. w{b}a{b} plans
    (leaf kernel 'lut_gemm' with a precomputed product LUT) run the
    paper-faithful path per expert: dynamic PER-TOKEN activation
    quantization — each (e, m) row's scale depends only on its own values,
    keeping outputs independent of the routed batch composition — then
    ``expert_lut_gemm``. The 'ref' backend keeps the algebraically identical
    dequant formulation so the SPMD dry-run sees shardable dense HLO. All
    kernel calls go through the kernels/registry dispatch surface."""
    from repro.core import packing
    from repro.kernels import registry as kreg
    k_pad = qw.packed.shape[-1] * packing.PACK_FACTOR[qw.bits]
    if k_pad != qw.in_features:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, k_pad - qw.in_features)))
    if qw.kernel == "lut_gemm" and qw.a_bits is not None and qw.plut is not None:
        G = qw.group_size
        a_scale = quant.group_scales(x.astype(jnp.float32),
                                     qw.a_bits, None)[..., None]  # (E, M, 1)
        aq = quant.quantize(x, a_scale, bits=qw.a_bits, signed=True)
        a_idx = quant.to_index(aq, qw.a_bits, True)
        if kreg.resolve_backend(backend) == "ref":
            a_deq = jnp.take(qw.a_levels, a_idx.astype(jnp.int32))
            w_deq = jnp.take(qw.codebook,
                             packing.unpack(qw.packed, qw.bits).astype(jnp.int32))
            if G is not None:
                w_deq = w_deq * quant.expand_group_scales(qw.scales, G)
            y = jnp.einsum("emk,enk->emn", a_deq, w_deq,
                           preferred_element_type=jnp.float32)
            return y * a_scale if G is not None \
                else y * qw.scales[:, None, :] * a_scale
        y = kreg.dispatch(
            "expert_lut_gemm", a_idx, qw.packed, qw.plut,
            qw.scales if G is not None else None,
            w_bits=qw.bits, a_bits=qw.a_bits, group_size=G,
            backend=backend, tp=qw.tp)
        return y * a_scale if G is not None \
            else y * qw.scales[:, None, :] * a_scale
    return kreg.dispatch(
        "expert_dequant_matmul", x, qw.packed, qw.codebook, qw.scales,
        bits=qw.bits, group_size=qw.group_size, backend=backend, tp=qw.tp)


def moe_apply(p: dict, x: jax.Array, *, cfg, mode: str = "plain") -> jax.Array:
    """x: (B, S, D). GShard dense-capacity dispatch: tokens grouped, top-k
    routing with capacity dropping, experts applied via einsum over the
    EP-sharded expert axis, combine via the gate-weighted inverse dispatch."""
    moe = cfg.moe
    B, S, D = x.shape
    E, K = moe.n_experts, moe.top_k
    T = B * S
    gs = min(moe.group_size, T)
    while T % gs:                 # largest divisor of T not above group_size
        gs -= 1
    Gn = T // gs
    import math
    C = max(4, 2 ** math.ceil(math.log2(max(gs * K * moe.capacity_factor / E, 1.0))))
    C = min(C, gs)
    pol = cfg.quant

    xg = x.reshape(Gn, gs, D)
    xg = shard(xg, "group", None, "embed_act")
    logits = (xg.astype(jnp.float32) @ p["w_router"])          # (G, gs, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_k, idx_k = jax.lax.top_k(probs, K)                     # (G, gs, K)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    # capacity assignment, slot by slot (k is small: <= 6)
    dispatch = jnp.zeros((Gn, gs, E, C), xg.dtype)
    combine = jnp.zeros((Gn, gs, E, C), jnp.float32)
    counts = jnp.zeros((Gn, E), jnp.int32)
    for j in range(K):
        oh = jax.nn.one_hot(idx_k[..., j], E, dtype=jnp.int32)      # (G, gs, E)
        pos = counts[:, None, :] + jnp.cumsum(oh, axis=1) - oh      # pos within expert
        keep = (pos < C) & (oh > 0)
        slot = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                              dtype=xg.dtype)[..., :C]              # (G,gs,E,C)
        slot = slot * keep[..., None].astype(xg.dtype)
        dispatch = dispatch + slot
        combine = combine + slot.astype(jnp.float32) * gate_k[..., j][..., None, None]
        counts = counts + oh.sum(axis=1)

    dispatch = shard(dispatch, "group", None, "experts_act", None)
    ein = jnp.einsum("gsd,gsec->egcd", xg, dispatch)                # (E, G, C, D)
    ein = shard(ein, "experts_act", "group", None, "embed_act")

    if any(isinstance(p[n], QuantizedWeight) and p[n].kernel is not None
           for n in ("we_gate", "we_up", "we_down")):
        # plan-covered expert path: packed weights stay packed in HBM and
        # run through the grouped kernel (w{b}a16 per expert; 'ref' backend
        # keeps the shardable einsum formulation for the dry-run). Dispatch
        # is PER LEAF: a mixed plan may route some projections through the
        # kernel and keep others bf16/legacy.
        be = plan_backend(pol)
        Ex, Gx, Cx, Dx = ein.shape
        xe = ein.reshape(Ex, Gx * Cx, Dx)

        def proj(name, xin):                                  # -> (E, M, N)
            leaf = p[name]
            if isinstance(leaf, QuantizedWeight) and leaf.kernel is not None:
                return _expert_matmul(leaf, xin.astype(x.dtype), be)   # f32
            w = _expert_w(p, name, pol=pol, mode=mode).astype(x.dtype)
            return jnp.einsum("emk,ekn->emn", xin.astype(x.dtype), w)

        g = proj("we_gate", xe)
        u = proj("we_up", xe)
        h = (jax.nn.silu(g) if cfg.mlp != "geglu" else jax.nn.gelu(g)) * u
        eo = proj("we_down", h).reshape(Ex, Gx, Cx, Dx)       # (E, G, C, D)
    else:
        wg = _expert_w(p, "we_gate", pol=pol, mode=mode).astype(x.dtype)
        wu = _expert_w(p, "we_up", pol=pol, mode=mode).astype(x.dtype)
        wd = _expert_w(p, "we_down", pol=pol, mode=mode).astype(x.dtype)
        g = jnp.einsum("egcd,edf->egcf", ein, wg)
        u = jnp.einsum("egcd,edf->egcf", ein, wu)
        h = (jax.nn.silu(g) if cfg.mlp != "geglu" else jax.nn.gelu(g)) * u
        eo = jnp.einsum("egcf,efd->egcd", h, wd)                    # (E, G, C, D)

    out = jnp.einsum("egcd,gsec->gsd", eo.astype(jnp.float32), combine)
    out = out.reshape(B, S, D).astype(x.dtype)
    out = checkpoint_name(shard(out, "batch", "seq_sp", "embed_act"), "block_out")
    if "shared" in p:
        out = out + mlp_apply(p["shared"], x, cfg=cfg, mode=mode, tag="moe.shared")
    return out


def moe_aux_loss(logits: jax.Array, idx_k: jax.Array, n_experts: int) -> jax.Array:
    """Load-balance auxiliary loss (GShard eq. 4 style)."""
    probs = jax.nn.softmax(logits, -1)
    me = probs.mean(axis=(0, 1))
    oh = jax.nn.one_hot(idx_k[..., 0], n_experts)
    ce = oh.mean(axis=(0, 1))
    return n_experts * jnp.sum(me * ce)
