"""Step functions: the jit units the launchers / dry-run lower.

train_step : QAT training step (LSQ fake-quant forward, grads incl. learned
             step sizes, global-norm clip, pluggable optimizer).
prefill_step / decode_step : serving with packed 2-bit weights (the paper's
             deployed form). decode_step is what the ``decode_*``/``long_*``
             cells lower.

All steps are pure (state in / state out) so they are jit/pjit-compatible
and donate-able.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs, optim
from repro.dist import sharding
from repro.models import lm


_BATCH_FWD_KEYS = ("positions", "audio_embed", "vision_embed")


def _fwd_kwargs(batch: dict) -> dict:
    return {k: batch[k] for k in _BATCH_FWD_KEYS if k in batch}


def make_loss_fn(cfg, *, mode: str = "qat"):
    def loss_fn(params, batch):
        h, _ = lm.forward(params, cfg, batch["tokens"], mode=mode,
                          **_fwd_kwargs(batch))
        return lm.chunked_ce_loss(params, cfg, h, batch["labels"])
    return loss_fn


def make_train_step(cfg, optimizer: optim.Optimizer, *, mode: str = "qat",
                    clip: float = 1.0):
    loss_fn = make_loss_fn(cfg, mode=mode)
    n_micro = max(1, cfg.microbatch)

    def grads_of(params, batch):
        if n_micro == 1:
            l, g = jax.value_and_grad(loss_fn)(params, batch)
            return l, sharding.constrain_like_params(g)
        # gradient accumulation: scan over microbatches; the remat history
        # (B_local/n_micro x S x D x L) shrinks by the microbatch factor —
        # what lets llama4-maverick train_4k fit 16 GB/chip (DESIGN.md §6).
        adt = jnp.dtype(cfg.accum_dtype)
        split = jax.tree.map(
            lambda x: x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:]),
            batch)

        def mb(carry, mbatch):
            acc, lsum = carry
            l, g = jax.value_and_grad(loss_fn)(params, mbatch)
            g = sharding.constrain_like_params(g)   # grads reduce-scatter
            acc = jax.tree.map(lambda a, b: a + b.astype(adt), acc, g)
            return (acc, lsum + l), None

        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params)
        (acc, lsum), _ = jax.lax.scan(mb, (acc0, jnp.zeros((), jnp.float32)),
                                      split)
        inv = 1.0 / n_micro
        return lsum * inv, jax.tree.map(lambda g: g * inv, acc)

    def train_step(state: dict, batch: dict):
        params, opt_state = state["params"], state["opt_state"]
        loss, grads = grads_of(params, batch)
        grads, gnorm = optim.clip_by_global_norm(grads, clip)
        updates, opt_state, om = optimizer.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, "grad_norm": gnorm, **om}
        return new_state, metrics

    return train_step


def init_dp_err(params, n_dp: int) -> dict:
    """Per-replica error-feedback residuals for compressed DP gradient
    reduction (one leading replica axis, sharded over the dp mesh axis)."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_dp,) + p.shape, jnp.float32), params)


def make_dp_train_step(cfg, optimizer: optim.Optimizer, mesh, *,
                       mode: str = "qat", clip: float = 1.0,
                       compressed: bool = False, axis: str = "data"):
    """Explicit data-parallel train step: shard_map over ``axis`` with the
    batch split across replicas and gradients mean-reduced across the wire.

    ``compressed=True`` routes the reduction through
    ``dist.collectives.compressed_psum`` — int8 block-64 codes on the wire
    (4x fewer DCN bytes than f32) with per-replica error feedback carried
    in ``state["dp_err"]`` (init via ``init_dp_err``; required only when
    compressed), so quantization bias telescopes across steps instead of
    accumulating. This is the ``--compressed-dp`` path of launch/train.py.
    """
    from repro.dist import collectives

    loss_fn = make_loss_fn(cfg, mode=mode)
    P = jax.sharding.PartitionSpec

    def step(state, batch):
        params, opt_state = state["params"], state["opt_state"]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = sharding.constrain_like_params(grads)
        loss = jax.lax.pmean(loss, axis)
        if compressed:
            flat_g, tdef = jax.tree_util.tree_flatten(grads)
            flat_e = tdef.flatten_up_to(state["dp_err"])
            pairs = [collectives.compressed_psum(g, axis, e[0])
                     for g, e in zip(flat_g, flat_e)]
            grads = jax.tree_util.tree_unflatten(tdef, [g for g, _ in pairs])
            new_err = jax.tree_util.tree_unflatten(
                tdef, [e[None] for _, e in pairs])
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
            new_err = None
        grads, gnorm = optim.clip_by_global_norm(grads, clip)
        updates, opt_state, om = optimizer.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1}
        if new_err is not None:
            new_state["dp_err"] = new_err
        metrics = {"loss": loss, "grad_norm": gnorm, **om}
        return new_state, metrics

    rep = P()
    state_spec = {"params": rep, "opt_state": rep, "step": rep}
    if compressed:
        state_spec["dp_err"] = P(axis)
    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(state_spec, P(axis)),
        out_specs=(state_spec, rep),
        check_vma=False)
    return sharded


def make_prefill_step(cfg, *, mode: str = "plain", max_len: Optional[int] = None):
    """(params, batch) -> (last-position logits, decode-ready caches).

    Caches are folded to decode form INSIDE the step: local-attention layers
    keep only their window-sized ring (gemma3: 40/48 layers drop from 32k to
    1k rows), which is what makes the 32k-prefill cells fit per-device HBM.
    """

    def prefill_step(params, batch):
        S = batch["tokens"].shape[1]
        h, caches = lm.forward(params, cfg, batch["tokens"], mode=mode,
                               collect_cache=True, **_fwd_kwargs(batch))
        with obs.scope("lm_head"):
            logits = lm.logits_fn(params, cfg, h[:, -1:])
        dec = lm.prefill_to_cache(cfg, caches, S, max_len or S)
        return logits, dec

    return prefill_step


def make_decode_step(cfg, *, mode: str = "plain"):
    """(params, caches, batch{tokens(B,1), pos(B,)}) -> (logits, caches)."""

    def decode_step(params, caches, batch):
        h, caches = lm.forward(params, cfg, batch["tokens"], mode=mode,
                               caches=caches, pos=batch["pos"],
                               **_fwd_kwargs(batch))
        logits = lm.logits_fn(params, cfg, h)
        return logits, caches

    return decode_step


def init_train_state(key, cfg, optimizer: optim.Optimizer, *,
                     mode: str = "qat") -> dict:
    params = lm.init_params(key, cfg, mode=mode)
    return {"params": params, "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(cfg, optimizer: optim.Optimizer, *, mode: str = "qat"):
    """ShapeDtypeStruct state tree — no allocation (dry-run)."""
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(
        functools.partial(init_train_state, cfg=cfg, optimizer=optimizer,
                          mode=mode), key)


def abstract_serve_params(cfg):
    """Quantized (packed) serving params as SDS — no allocation."""
    key = jax.random.PRNGKey(0)

    def build(key):
        p = lm.init_params(key, cfg, mode="plain")
        return lm.quantize_tree(p, cfg)

    return jax.eval_shape(build, key)


def abstract_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    return jax.eval_shape(
        functools.partial(lm.init_cache, cfg, batch, max_len, dtype))
