"""Model assembly: decoder-only LM and encoder-decoder (whisper), built from
ModelConfig. All 10 assigned architectures instantiate through this module.

Layer stacking: the per-layer `pattern` (e.g. gemma3's 5 local + 1 global)
defines a *superblock*; parameters for the n_superblocks repeats are stacked
on a leading axis and iterated with jax.lax.scan (O(1) HLO size for 48-layer
models). Remainder layers (38 = 12*3 + 2 for recurrentgemma) get their own
stacked scan over the pattern prefix.

Decode caches mirror the parameter stacking so the same scan walks
(params, cache) together.

Serving transformation: `quantize_tree` replaces every dense `{"w": ...}`
that the QuantPolicy covers with `{"qw": QuantizedWeight}` (packed sub-byte
codes + codebook + per-channel scales) — the paper's offline weight
packing/quantization step.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import qlinear
from repro.dist.sharding import shard
from . import layers as L
from . import recurrent as R


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #

def _layer_init(key, cfg, layer_type: str, is_moe: bool, *, mode: str,
                dtype, cross: bool) -> dict:
    ks = jax.random.split(key, 6)
    p = {}
    if layer_type == "rwkv":
        p["rwkv"] = R.rwkv_init(ks[0], cfg, mode=mode, dtype=dtype)
        return p
    p["ln1"] = L.norm_init(cfg.d_model, cfg.norm, dtype)
    if layer_type == "recurrent":
        p["rnn"] = R.rglru_init(ks[0], cfg, mode=mode, dtype=dtype)
    else:
        p["attn"] = L.attn_init(ks[0], cfg, mode=mode, dtype=dtype)
    if cross:
        p["ln_x"] = L.norm_init(cfg.d_model, cfg.norm, dtype)
        p["cross"] = L.attn_init(ks[1], cfg, mode=mode, dtype=dtype, cross=True)
    p["ln2"] = L.norm_init(cfg.d_model, cfg.norm, dtype)
    if is_moe:
        p["moe"] = L.moe_init(ks[2], cfg, mode=mode, dtype=dtype)
    else:
        p["mlp"] = L.mlp_init(ks[3], cfg, mode=mode, dtype=dtype)
    return p


def _superblock_init(key, cfg, pattern, moe_flags, *, mode, dtype, cross):
    ks = jax.random.split(key, len(pattern))
    return {f"l{i}": _layer_init(ks[i], cfg, pattern[i], moe_flags[i],
                                 mode=mode, dtype=dtype, cross=cross)
            for i in range(len(pattern))}


def _stacked_init(key, cfg, n: int, pattern, moe_flags, *, mode, dtype, cross):
    keys = jax.random.split(key, n)
    fn = functools.partial(_superblock_init, cfg=cfg, pattern=pattern,
                           moe_flags=moe_flags, mode=mode, dtype=dtype,
                           cross=cross)
    return jax.vmap(fn)(keys)


def init_params(key, cfg, *, mode: str = "plain") -> dict:
    """Full parameter tree. mode: 'plain' | 'qat' (attaches LSQ steps)."""
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    D, V = cfg.d_model, cfg.vocab_size
    pattern = cfg.pattern
    mp = cfg.moe_pattern or ((True,) * len(pattern) if cfg.moe else (False,) * len(pattern))
    n_sb, n_rem = cfg.n_superblocks, cfg.n_remainder

    embed_name = "tok_embed" if cfg.tie_embeddings else "in_embed"
    p: dict = {
        embed_name: jax.random.normal(ks[0], (V, D), dtype) * 0.02,
        "final_norm": L.norm_init(D, cfg.norm, dtype),
    }
    if cfg.pos_embed == "learned":
        p["pos_embed"] = jax.random.normal(ks[1], (cfg.max_pos, D), dtype) * 0.02
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": jax.random.normal(ks[2], (D, V), dtype) * (D ** -0.5)}

    cross = cfg.is_encdec
    if n_sb:
        p["blocks"] = _stacked_init(ks[3], cfg, n_sb, pattern, mp,
                                    mode=mode, dtype=dtype, cross=cross)
    if n_rem:
        p["rem"] = _stacked_rem_init(ks[4], cfg, pattern[:n_rem], mp[:n_rem],
                                     mode=mode, dtype=dtype, cross=cross)

    if cfg.is_encdec:
        enc_cfg = dataclasses.replace(cfg, qkv_bias=False, moe=None,
                                      pattern=("global",), moe_pattern=None)
        p["encoder"] = {
            "pos_embed": jax.random.normal(ks[5], (cfg.encoder_seq, D), dtype) * 0.02,
            "blocks": _stacked_init(ks[6], enc_cfg, cfg.encoder_layers,
                                    ("global",), (False,), mode=mode,
                                    dtype=dtype, cross=False),
            "final_norm": L.norm_init(D, cfg.norm, dtype),
        }
    return p


def _stacked_rem_init(key, cfg, rem_pattern, rem_moe, *, mode, dtype, cross):
    """Remainder layers: heterogenous in general -> per-layer dict (unrolled)."""
    ks = jax.random.split(key, len(rem_pattern))
    return {f"r{i}": _layer_init(ks[i], cfg, rem_pattern[i], rem_moe[i],
                                 mode=mode, dtype=dtype, cross=cross)
            for i in range(len(rem_pattern))}


# --------------------------------------------------------------------------- #
# Cache init (decode)
# --------------------------------------------------------------------------- #

def _layer_cache(cfg, layer_type: str, batch: int, max_len: int, dtype,
                 cross: bool) -> dict:
    KV, hd = cfg.n_kv_heads, cfg.hd
    c: dict = {}
    if layer_type == "rwkv":
        c["rwkv"] = R.rwkv_state_init(cfg, batch, dtype)
        return c
    if layer_type == "recurrent":
        c["rnn"] = R.rglru_state_init(cfg, batch, dtype)
    else:
        S = min(max_len, cfg.window) if layer_type == "local" else max_len
        if cfg.kv_cache_dtype == "int8":
            c["attn"] = {"k": jnp.zeros((batch, S, KV, hd), jnp.int8),
                         "v": jnp.zeros((batch, S, KV, hd), jnp.int8),
                         "k_sc": jnp.zeros((batch, S, KV), jnp.float32),
                         "v_sc": jnp.zeros((batch, S, KV), jnp.float32)}
        elif cfg.kv_cache_dtype == "int4":
            c["attn"] = {"k": jnp.zeros((batch, S, KV, hd // 2), jnp.uint8),
                         "v": jnp.zeros((batch, S, KV, hd // 2), jnp.uint8),
                         "k_sc": jnp.zeros((batch, S, KV), jnp.float32),
                         "v_sc": jnp.zeros((batch, S, KV), jnp.float32)}
        else:
            c["attn"] = {"k": jnp.zeros((batch, S, KV, hd), dtype),
                         "v": jnp.zeros((batch, S, KV, hd), dtype)}
    if cross:
        c["cross"] = {"xk": jnp.zeros((batch, cfg.encoder_seq, KV, hd), dtype),
                      "xv": jnp.zeros((batch, cfg.encoder_seq, KV, hd), dtype)}
    return c


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Decode cache tree, stacked to mirror the param structure."""
    pattern, n_sb, n_rem = cfg.pattern, cfg.n_superblocks, cfg.n_remainder
    cross = cfg.is_encdec

    def sb():
        return {f"l{i}": _layer_cache(cfg, pattern[i], batch, max_len, dtype, cross)
                for i in range(len(pattern))}

    out: dict = {}
    if n_sb:
        one = sb()
        out["blocks"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_sb,) + x.shape), one)
    if n_rem:
        out["rem"] = {f"r{i}": _layer_cache(cfg, pattern[i], batch, max_len,
                                            dtype, cross)
                      for i in range(n_rem)}
    return out


# --------------------------------------------------------------------------- #
# Layer / superblock apply
# --------------------------------------------------------------------------- #

def _apply_layer(p: dict, x, *, cfg, layer_type, is_moe, mode, positions,
                 enc_out, cache, pos, segments=None, block_tables=None,
                 ring_tables=None, kv_splits=None):
    new_cache: dict = {}
    if layer_type == "rwkv":
        y, st = R.rwkv_apply(p["rwkv"], x, cfg=cfg, mode=mode,
                             state=cache.get("rwkv") if cache else None)
        new_cache["rwkv"] = st
        return y, new_cache

    h = L.norm_apply(p["ln1"], x, cfg.norm)
    if layer_type == "recurrent":
        y, st = R.rglru_apply(p["rnn"], h, cfg=cfg, mode=mode,
                              state=cache.get("rnn") if cache else None)
        new_cache["rnn"] = st
    else:
        y, kv = L.attn_apply(p["attn"], h, cfg=cfg, layer_type=layer_type,
                             mode=mode, positions=positions,
                             cache=cache.get("attn") if cache else None,
                             pos=pos, segments=segments,
                             block_tables=block_tables,
                             ring_tables=ring_tables, kv_splits=kv_splits)
        if kv is not None:
            new_cache["attn"] = kv
    x = x + y

    if "cross" in p:
        hx = L.norm_apply(p["ln_x"], x, cfg.norm)
        xc = cache.get("cross") if cache else None
        y, xkv = L.attn_apply(p["cross"], hx, cfg=cfg, mode=mode,
                              enc_out=enc_out, cache=xc, pos=pos)
        if xkv is not None:
            new_cache["cross"] = xkv
        elif xc is not None:
            new_cache["cross"] = xc     # pass cross-KV through decode steps
        x = x + y

    h2 = L.norm_apply(p["ln2"], x, cfg.norm)
    if is_moe:
        y2 = L.moe_apply(p["moe"], h2, cfg=cfg, mode=mode)
    else:
        y2 = L.mlp_apply(p["mlp"], h2, cfg=cfg, mode=mode)
    return x + y2, new_cache


def _apply_superblock(p: dict, x, cache, *, cfg, pattern, moe_flags, mode,
                      positions, enc_out, pos, segments=None,
                      block_tables=None, ring_tables=None, kv_splits=None):
    new_cache = {}
    for i, lt in enumerate(pattern):
        lc = cache.get(f"l{i}") if cache else None
        x, nc = _apply_layer(p[f"l{i}"], x, cfg=cfg, layer_type=lt,
                             is_moe=moe_flags[i], mode=mode,
                             positions=positions, enc_out=enc_out,
                             cache=lc, pos=pos, segments=segments,
                             block_tables=block_tables,
                             ring_tables=ring_tables, kv_splits=kv_splits)
        new_cache[f"l{i}"] = nc
    return x, new_cache


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if policy == "names":
        # save only the named post-TP-collective block outputs (seq_sp-
        # sharded, 42 MB each for llama4) -> the backward pass never re-runs
        # the forward all-reduces/gathers that full remat would repeat.
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "block_out"))
    # "full": save nothing inside the superblock; only scan carries persist.
    return jax.checkpoint(fn)


def encoder_forward(p: dict, cfg, audio_embed: jax.Array, *, mode: str):
    """Whisper-style encoder over stub frame embeddings (B, T, D)."""
    enc_cfg = dataclasses.replace(cfg, qkv_bias=False, moe=None,
                                  pattern=("global",), moe_pattern=None,
                                  pos_embed="learned")
    x = audio_embed.astype(jnp.dtype(cfg.dtype))
    x = x + p["pos_embed"][None, : x.shape[1]].astype(x.dtype)

    def body(x, bp):
        h = L.norm_apply(bp["l0"]["ln1"], x, cfg.norm)
        # non-causal self attention
        B, S, D = h.shape
        KV, H, hd = cfg.n_kv_heads, cfg.n_heads, cfg.hd
        q = L.dense(bp["l0"]["attn"]["wq"], h, tag="attn.wq", policy=cfg.quant,
                    mode=mode).reshape(B, S, KV, H // KV, hd)
        k = L.dense(bp["l0"]["attn"]["wk"], h, tag="attn.wk", policy=cfg.quant,
                    mode=mode).reshape(B, S, KV, hd)
        v = L.dense(bp["l0"]["attn"]["wv"], h, tag="attn.wv", policy=cfg.quant,
                    mode=mode).reshape(B, S, KV, hd)
        o = L.flash_attention(q, k, v, causal=False).reshape(B, S, H * hd)
        x = x + L.dense(bp["l0"]["attn"]["wo"], o, tag="attn.wo",
                        policy=cfg.quant, mode=mode)
        h2 = L.norm_apply(bp["l0"]["ln2"], x, cfg.norm)
        x = x + L.mlp_apply(bp["l0"]["mlp"], h2, cfg=enc_cfg, mode=mode)
        return x, None

    x, _ = jax.lax.scan(_remat(body, cfg.remat), x, p["blocks"])
    return L.norm_apply(p["final_norm"], x, cfg.norm)


def forward(
    params: dict,
    cfg,
    tokens: jax.Array,                       # (B, S)
    *,
    mode: str = "plain",
    positions: Optional[jax.Array] = None,   # (B,S) or (B,S,3) M-RoPE
    audio_embed: Optional[jax.Array] = None,
    vision_embed: Optional[jax.Array] = None,
    caches: Optional[dict] = None,
    pos: Optional[jax.Array] = None,         # (B,) decode position
    segments: Optional[jax.Array] = None,    # (B,S) sequence-packing ids
    collect_cache: bool = False,
    block_tables: Optional[jax.Array] = None,  # (B, nb) paged-cache tables
    ring_tables: Optional[jax.Array] = None,   # (B, ring_len) local-layer ring
    kv_splits: Optional[int] = None,           # static flash-decode splits
):
    """Token ids -> final hidden states (B, S, D). Returns (hidden, new_caches).

    Train/prefill: caches=None (collect_cache=True to get prefill KV).
    Decode: caches given, S == 1, pos (B,).
    Paged serving (serving/engine.py): caches hold shared block pools,
    block_tables map each batch row's logical blocks to physical blocks;
    S == 1 is a batched decode step, S > 1 the batched chunk math with
    per-row start positions `pos` (B,). S need not be block-aligned: the
    engine's speculative VERIFY step is exactly this path with
    S == spec_k + 1, scattering the draft tokens' K/V through (widened)
    tables and keeping the returned hidden states at every position so
    `logits_fn` can score all spec_k + 1 candidates in one forward.
    """
    B, S = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    table = params.get("tok_embed", params.get("in_embed"))
    with obs.scope("embed"):
        x = jnp.take(table, tokens, axis=0).astype(dtype)
        x = shard(x, "batch", "seq_sp", "embed_act")
        if vision_embed is not None:
            nv = vision_embed.shape[1]
            x = jnp.concatenate([vision_embed.astype(dtype), x[:, nv:]],
                                axis=1)
        if cfg.pos_embed == "learned":
            if pos is None:
                x = x + params["pos_embed"][None, :S].astype(dtype)
            else:
                x = x + jnp.take(params["pos_embed"], pos,
                                 axis=0)[:, None].astype(dtype)

    enc_out = None
    if cfg.is_encdec and audio_embed is not None:
        enc_out = encoder_forward(params["encoder"], cfg, audio_embed, mode=mode)

    mp = cfg.moe_pattern or ((True,) * len(cfg.pattern) if cfg.moe
                             else (False,) * len(cfg.pattern))
    sb_fn = functools.partial(_apply_superblock, cfg=cfg, pattern=cfg.pattern,
                              moe_flags=mp, mode=mode, positions=positions,
                              enc_out=enc_out, pos=pos, segments=segments,
                              block_tables=block_tables,
                              ring_tables=ring_tables, kv_splits=kv_splits)

    new_caches: dict = {}
    with obs.scope("layers"):  # the scan: its slicing and stacking
        if "blocks" in params:
            decode = caches is not None

            def body(x, pc):
                bp, bc = pc
                x, nc = sb_fn(bp, x, bc)
                out = nc if (decode or collect_cache) else None
                return x, out

            cache_in = caches["blocks"] if decode else None
            remat = cfg.remat if not decode else "none"
            n_sb = cfg.n_superblocks
            if (remat == "2level" and not decode and not collect_cache
                    and n_sb % max(cfg.remat_group, 1) == 0
                    and cfg.remat_group > 1):
                # two-level (sqrt-ish) remat: outer scan saves only every
                # remat_group-th residual; the inner scan re-runs under its
                # own checkpoint during backward. Trades ~2x layer recompute
                # for a remat_group-x smaller activation history — the knob
                # that fits llama4-maverick train_4k (EXPERIMENTS.md §Perf).
                G = cfg.remat_group
                grouped = jax.tree.map(
                    lambda p: p.reshape(n_sb // G, G, *p.shape[1:]),
                    params["blocks"])

                def inner(x, gp):
                    x, _ = jax.lax.scan(_remat(body, "full"), x, (gp, None))
                    return x, None

                x, _ = jax.lax.scan(jax.checkpoint(inner), x, grouped)
                stacked_cache = None
            else:
                if remat == "2level":
                    remat = "full"
                x, stacked_cache = jax.lax.scan(
                    _remat(body, remat), x, (params["blocks"], cache_in))
            if stacked_cache is not None:
                new_caches["blocks"] = stacked_cache

        if "rem" in params:
            rem_cache = {}
            for i in range(cfg.n_remainder):
                lc = caches["rem"][f"r{i}"] if caches else None
                lt = cfg.pattern[i]
                x, nc = _apply_layer(params["rem"][f"r{i}"], x, cfg=cfg,
                                     layer_type=lt, is_moe=mp[i], mode=mode,
                                     positions=positions, enc_out=enc_out,
                                     cache=lc, pos=pos, segments=segments,
                                     block_tables=block_tables,
                                     ring_tables=ring_tables,
                                     kv_splits=kv_splits)
                rem_cache[f"r{i}"] = nc
            if caches is not None or collect_cache:
                new_caches["rem"] = rem_cache

    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    return x, (new_caches or None)


def prefill_to_cache(cfg, prefill_caches: dict, prefill_len: int,
                     max_len: int) -> dict:
    """Convert collect_cache=True prefill output (full-length K/V, recurrent
    states) into decode buffers: global attention K/V padded to max_len,
    local attention K/V folded into a W-slot ring (slot = t mod W).

    Window contract shared with the block-granular ring in serving/cache.py
    (``Engine(ring=True)``): both keep exactly the rows with
    t > pos - window, so for the same prompt the fold-based dense decode,
    the full-table paged engine, and the ring-paged engine all attend over
    the SAME key set and emit identical argmax tokens. They are not bitwise
    identical on logits — this fold sums softmax terms in t mod W order,
    the block ring in its own rotated order — which is why ring mode is
    opt-in and pinned by token-level tests (tests/test_ring_paged.py)."""

    def fold(kv: jax.Array, is_local: bool) -> jax.Array:
        # kv: (..., S, KV, hd); seq axis = -3
        S = kv.shape[-3]
        if not is_local:
            pad = [(0, 0)] * kv.ndim
            pad[-3] = (0, max_len - S)
            return jnp.pad(kv, pad)
        W = min(max_len, cfg.window)
        L = min(S, W)
        last = jax.lax.slice_in_dim(kv, S - L, S, axis=kv.ndim - 3)
        if L < W:
            pad = [(0, 0)] * kv.ndim
            pad[-3] = (0, W - L)
            last = jnp.pad(last, pad)
        shift = (S - L) % W
        return jnp.roll(last, shift, axis=kv.ndim - 3)

    def walk(tree, layer_type):
        out = {}
        for k, v in tree.items():
            if k == "attn":
                folded = {kk: fold(vv, layer_type == "local")
                          for kk, vv in v.items()}
                if cfg.kv_cache_dtype in L.KV_QUANT:
                    qf = L.KV_QUANT[cfg.kv_cache_dtype][0]
                    k8, ksc = qf(folded["k"])
                    v8, vsc = qf(folded["v"])
                    folded = {"k": k8, "v": v8, "k_sc": ksc, "v_sc": vsc}
                out[k] = folded
            elif k in ("rnn", "rwkv", "cross"):
                out[k] = v
            elif isinstance(v, dict):
                out[k] = walk(v, layer_type)
            else:
                out[k] = v
        return out

    result: dict = {}
    with obs.scope("cache_fold"):
        if "blocks" in prefill_caches:
            result["blocks"] = {
                f"l{i}": walk(prefill_caches["blocks"][f"l{i}"],
                              cfg.pattern[i])
                for i in range(len(cfg.pattern))}
        if "rem" in prefill_caches:
            result["rem"] = {
                f"r{i}": walk(prefill_caches["rem"][f"r{i}"], cfg.pattern[i])
                for i in range(cfg.n_remainder)}
    return result


# --------------------------------------------------------------------------- #
# Heads and losses
# --------------------------------------------------------------------------- #

def logits_fn(params: dict, cfg, hidden: jax.Array) -> jax.Array:
    """(B, S, D) -> (B, S, V), vocab-sharded."""
    with obs.scope("lm_head"):
        if cfg.tie_embeddings:
            w = params["tok_embed"]                          # (V, D)
            out = jnp.einsum("bsd,vd->bsv", hidden, w,
                             preferred_element_type=jnp.float32)
        else:
            p = params["lm_head"]
            w = (qlinear.dequant_weight(p["qw"]).astype(hidden.dtype)
                 if "qw" in p else p["w"])
            out = jnp.einsum("bsd,dv->bsv", hidden, w,
                             preferred_element_type=jnp.float32)
        return shard(out, "batch", "seq", "vocab_act")


def chunked_ce_loss(params: dict, cfg, hidden: jax.Array, labels: jax.Array,
                    chunk: int = 512) -> jax.Array:
    """Cross-entropy over seq chunks — never materializes (B, S, V) f32 for
    the 262k-vocab archs. Returns mean loss."""
    B, S, D = hidden.shape
    c = min(chunk, S)
    while S % c:
        c //= 2
    n = S // c
    hs = hidden.reshape(B, n, c, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, c).transpose(1, 0, 2)

    def one(args):
        h, l = args
        lg = logits_fn(params, cfg, h)                      # (B, c, V) f32
        lse = jax.nn.logsumexp(lg, axis=-1)
        valid = l >= 0                                      # -1: masked
        tgt = jnp.take_along_axis(lg, jnp.maximum(l, 0)[..., None],
                                  axis=-1)[..., 0]
        return (jnp.where(valid, lse - tgt, 0.0).sum(),
                valid.sum().astype(jnp.float32))

    if n == 1:
        total, count = one((hs[0], ls[0]))
    else:
        # checkpoint: backward recomputes each chunk's logits instead of
        # stacking an (n, B, c, V) f32 history (3.3 GB for llama4)
        totals, counts = jax.lax.map(jax.checkpoint(one), (hs, ls))
        total, count = totals.sum(), counts.sum()
    return total / jnp.maximum(count, 1.0)


# --------------------------------------------------------------------------- #
# Serving transformation: offline weight quantize+pack (the paper's step)
# --------------------------------------------------------------------------- #

def quantize_tree(params, cfg, *, tp: int = 1,
                  act_scales: Optional[dict] = None,
                  tune_cache: Optional[dict] = None) -> dict:
    """Replace plan-covered dense {"w": ...} with {"qw": QuantizedWeight}.
    Expert tensors (we_gate/we_up/we_down) are packed per-expert. LSQ steps
    are dropped (training-only).

    ``cfg.quant`` may be a single QuantPolicy (legacy: every covered layer
    gets the same format and the historical dequant-einsum forward) or a
    qplan.QuantPlan (ordered tag -> policy table: each layer class gets its
    own bits/group-size/kernel, resolved here, offline — the hot path only
    ever sees the precomputed leaves).

    ``tp`` packs the tree for an N-way tensor-parallel mesh: each leaf is
    stamped with its Megatron role (dist.sharding.TP_ROLES — 'col' shards
    the output dim, 'row' the contraction dim) and row-parallel layers get
    extra K padding so packed bytes AND scale-group boundaries align to the
    shard split (a group never straddles two devices). Layers whose output
    dim does not divide ``tp`` stay replicated (role None) — the same
    fallback-not-error policy as dist.sharding.

    ``act_scales`` (from ``calibrate_act_scales``) supplies per-layer-class
    activation amax stats; policies with ``a_scale='static'`` fold the
    calibrated scale into the leaf (``QuantizedWeight.a_sc``) instead of
    quantizing activations with a per-token dynamic scale.

    When the plan's ``tune`` field lists M buckets, the Pallas tile
    autotuner (kernels/autotune) runs here — offline, per distinct
    (kernel, M, K, N, bits, G) problem — and the winning blocks are stamped
    on each leaf's ``tiles`` aux for ``dense_serve`` to look up at trace
    time. ``tune_cache`` shares/persists the measurement memo across calls
    (kept small: repeated layer shapes tune once)."""
    from repro.core import calibrate, qplan
    from repro.dist.sharding import TP_ROLES
    from repro.kernels import autotune

    pol = cfg.quant
    if isinstance(pol, qlinear.QuantPolicy) and pol.w_bits is None:
        return params

    tune_ms = tuple(getattr(pol, "tune", ()) or ())
    tune_backend = qplan.plan_backend(pol)
    tile_cache = tune_cache if tune_cache is not None else {}

    def stamp_tiles(qw, lp):
        if not tune_ms or qw.kernel not in autotune.TUNABLE_OPS:
            return qw
        tiles = autotune.tune_leaf_tiles(
            qw.kernel, qw.k_padded, qw.out_features, bits=qw.bits,
            a_bits=lp.a_bits, group_size=qw.group_size, m_buckets=tune_ms,
            backend=tune_backend, cache=tile_cache)
        return dataclasses.replace(qw, tiles=tiles) if tiles else qw

    def role_for(name: str, out_dim: int) -> Optional[str]:
        if tp <= 1:
            return None
        role = TP_ROLES.get(name)
        if role == "col" and out_dim % tp:
            return None                     # divisibility fallback: replicate
        return role

    def static_for(tag, lp) -> Optional[float]:
        if (lp.a_scale != "static" or lp.a_bits is None
                or lp.resolved_kernel() != "lut_gemm"):
            return None
        amax = calibrate.lookup(act_scales, tag)
        if amax is None:
            return None                     # uncalibrated layer: dynamic
        return calibrate.static_scale(amax, lp.a_bits)

    def qdense(w, lp, role, a_static):
        # leading stack dims from scan-over-superblocks -> vmap the packer
        fn = functools.partial(qlinear.quantize_weight, policy=lp,
                               tp_role=role, tp_shards=tp, a_static=a_static)
        for _ in range(w.ndim - 2):
            fn = jax.vmap(fn)
        return stamp_tiles(fn(w), lp)

    def qexpert(w, lp, role):
        fn = functools.partial(qlinear.quantize_expert_weight, policy=lp,
                               tp_role=role, tp_shards=tp)
        for _ in range(w.ndim - 3):
            fn = jax.vmap(fn)
        return fn(w)

    def walk(tree, path=""):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                tag = f"{path}.{k}" if path else k
                if k in ("we_gate", "we_up", "we_down"):
                    # resolve expert leaves under the canonical
                    # '...moe.experts.<leaf>' tag — the SAME 'moe.experts'
                    # class moe_init/_expert_w resolve for QAT, so plan
                    # rules (and legacy skip lists) naming 'experts' agree
                    # between training and packing
                    lp = pol.policy_for(f"{path}.experts.{k}" if path
                                        else f"experts.{k}")
                    if lp is not None and hasattr(v, "ndim") and v.ndim >= 3:
                        out[k] = qexpert(v, lp, role_for(k, v.shape[-1]))
                    else:
                        out[k] = v
                    continue
                lp = pol.policy_for(tag)
                if (isinstance(v, dict) and "w" in v and
                        hasattr(v["w"], "ndim") and v["w"].ndim >= 2 and
                        lp is not None):
                    q = {"qw": qdense(v["w"], lp,
                                      role_for(k, v["w"].shape[-1]),
                                      static_for(tag, lp))}
                    if "b" in v:
                        q["b"] = v["b"]
                    out[k] = q
                elif k.endswith("_step"):
                    continue
                else:
                    out[k] = walk(v, tag)
            return out
        return tree

    return walk(params)


def calibrate_act_scales(params, cfg, batches, *, mode: str = "plain") -> dict:
    """Offline activation-range calibration pass (static activation scales).

    Runs the bf16 forward over ``batches`` (each a dict with at least
    "tokens") inside a ``core.calibrate.collect_act_stats`` context and
    returns the per-layer-class amax dict to hand to ``quantize_tree(...,
    act_scales=...)``. Stats are keyed by the dense-call tags ("attn.wq",
    "mlp.w_up", ...), i.e. one range per layer class — the granularity
    plans are written in."""
    from repro.core import calibrate

    with calibrate.collect_act_stats() as stats:
        for batch in batches:
            h, _ = forward(params, cfg, batch["tokens"], mode=mode,
                           positions=batch.get("positions"),
                           audio_embed=batch.get("audio_embed"),
                           vision_embed=batch.get("vision_embed"))
            jax.block_until_ready(h)
    return dict(stats)
