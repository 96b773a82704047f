"""Plain reference of the Qwen1.5 decoder, in float32 at the highest matmul
precision, for the benchmark's comparison that decides ``correct``.

It follows the published architecture (hf:Qwen/Qwen1.5-0.5B): token
embedding, per layer RMSNorm -> attention with QKV bias and rotary
positions (rotate-half, theta from the file) -> residual -> RMSNorm ->
SwiGLU MLP -> residual, a final RMSNorm and the tied embedding as the
head. On top of it, the quantisation the configuration file states under
``quant``, written here from that statement and not taken from the program:

- weights: each output channel's codes are ``clip(round(w / s), -2^(b-1),
  2^(b-1) - 1)`` with ``s = max|w| / 2^(b-1)`` over the channel's inputs,
  and the layer computes with ``codes * s`` (``weight_bits``);
- activations (``act_bits`` set): each token row of a packed linear's
  input is quantised the same way with its own ``max|x|`` scale;
- the key/value cache (``kv_cache_levels`` n): each (token, head) vector
  is held as ``clip(round(x / s), -n, n) * s`` with ``s = max|x| / n``
  before attention reads it, the current token's included;
- embedding, norms and head stay in the weights' own bfloat16 values.

Everything else is float32. No cache, no batching, no kernels: one full
causal forward over a whole sequence. ``act_dtype`` rounds the activations
the configuration keeps in bfloat16 (the residual stream and the input of
every linear) to a lower dtype: the control of the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512          # query rows per attention block (bounds the score tile)


def _fake_quant(x, qmin: int, qmax: int, axis: int = -1):
    """Max-abs fake quantisation along ``axis``: ``clip(round(x / s), qmin,
    qmax) * s`` with ``s = max(max|x| / max(-qmin, qmax), 1e-8)``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True)
                    / max(-qmin, qmax), 1e-8)
    return jnp.clip(jnp.round(x / s), qmin, qmax) * s


def _bits_quant(x, bits: int, axis: int = -1):
    """Signed ``bits``-bit codes in [-2^(b-1), 2^(b-1) - 1], scale
    ``max|x| / 2^(b-1)`` (the positive extreme clips one level short)."""
    return _fake_quant(x, -(2 ** (bits - 1)), 2 ** (bits - 1) - 1, axis)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x (T, H, hd), rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd // 2, dtype=jnp.float32)
                           / (hd // 2)))
    ang = positions[:, None].astype(jnp.float32) * inv         # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention, q/k/v (T, H, hd) -> (T, H * hd), in
    blocks of Q_BLOCK query rows."""
    T, H, hd = q.shape
    nb = T // Q_BLOCK
    kpos = jnp.arange(T)

    def block(args):
        i, qb = args                                          # (Qb, H, hd)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (jnp.arange(nb),
                              q.reshape(nb, Q_BLOCK, H, hd)))
    return out.reshape(T, H * hd)


@functools.partial(jax.jit, static_argnames=("sizes", "quant", "act_dtype"))
def _hidden(weights, tokens, *, sizes, quant, act_dtype):
    sizes, quant = dict(sizes), dict(quant)
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    hd = D // H
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    wb, ab, kvn = (quant["weight_bits"], quant["act_bits"],
                   quant["kv_cache_levels"])
    T = tokens.shape[0]
    positions = jnp.arange(T)

    def keep(x):
        # the activations the configuration holds in bfloat16; the control
        # rounds them to a lower dtype, the reference keeps them in float32
        if act_dtype is None:
            return x
        return x.astype(act_dtype).astype(jnp.float32)

    def linear(x, p):
        x = keep(x)
        if ab is not None:
            x = _bits_quant(x, ab)
        w = _bits_quant(p["w"].astype(jnp.float32), wb, axis=0)
        y = x @ w
        if "b" in p:
            y = y + p["b"].astype(jnp.float32)
        return y

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = linear(h, a["wq"]).reshape(T, H, hd)
        k = linear(h, a["wk"]).reshape(T, H, hd)
        v = linear(h, a["wv"]).reshape(T, H, hd)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        k, v = _fake_quant(k, -kvn, kvn), _fake_quant(v, -kvn, kvn)
        x = keep(x + linear(_attention(q, k, v), a["wo"]))
        m = p["mlp"]
        h = _rms(x, p["ln2"]["scale"], eps)
        g = jax.nn.silu(linear(h, m["w_gate"])) * linear(h, m["w_up"])
        return keep(x + linear(g, m["w_down"])), None

    x = keep(weights["tok_embed"][tokens].astype(jnp.float32))
    x, _ = jax.lax.scan(layer, x, weights["blocks"]["l0"])
    return _rms(x, weights["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("act_dtype",))
def _head(weights, h, rows, *, act_dtype):
    h = h[rows]
    if act_dtype is not None:
        h = h.astype(act_dtype).astype(jnp.float32)
    return h @ weights["tok_embed"].astype(jnp.float32).T


def logits(weights, sizes: dict, quant: dict, tokens: np.ndarray,
           rows: np.ndarray, *, length: int, n_rows: int,
           act_dtype=None) -> np.ndarray:
    """(len(rows), V) float32 logits at positions ``rows`` of one causal
    forward over ``tokens``, padded at the end to ``length`` (a multiple of
    Q_BLOCK; padding cannot reach earlier positions), with ``rows`` padded
    to ``n_rows``: every sequence of a cell runs the same two programs."""
    if length % Q_BLOCK or len(tokens) > length or len(rows) > n_rows:
        raise ValueError(f"length {length}, n_rows {n_rows}: tokens "
                         f"{len(tokens)}, rows {len(rows)}")
    tok = np.zeros((length,), np.int32)
    tok[:len(tokens)] = tokens
    r = np.zeros((n_rows,), np.int32)
    r[:len(rows)] = rows
    key = lambda d: tuple(sorted(d.items()))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        h = _hidden(weights, jnp.asarray(tok), sizes=key(sizes),
                    quant=key(quant), act_dtype=act_dtype)
        out = _head(weights, h, jnp.asarray(r), act_dtype=act_dtype)
    return np.asarray(out[:len(rows)], np.float32)
