"""Layer names of the model step (``repro.obs.scope``) on a small qwen1.5
served as the benchmark serves it: weights packed at w2a16, int8 KV cache,
the fixed-batch ``prefill_step`` and ``decode_step`` of launch/steps.py.

- every operation the model code emits carries one name of the table, and
  each step shows the names it should;
- the names are metadata only: with ``obs.scope`` patched to a no-op the
  compiled program is the same instruction for instruction once
  ``frontend_attributes`` and ``metadata`` are stripped, and the logits
  and caches are bit-identical.
"""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core

from repro import obs
from repro.configs import get_config, reduce_for_smoke
from repro.core.qplan import get_plan
from repro.launch import steps as St
from repro.models import lm

NAMES = {"embed", "layers", "norm", "attn/qkv", "attn/kv_write", "attn/core",
         "attn/out", "mlp/up", "mlp/down", "lm_head", "cache_fold"}
# the names each step shows: the prefill collects K/V without an op of its
# own (the scan stacks them) and folds them into the cache; a decode step
# writes the cache and folds nothing
SEEN = {"prefill": NAMES - {"attn/kv_write"},
        "decode": NAMES - {"cache_fold"}}
B, P, G = 2, 16, 4
_ATTRS = re.compile(
    r", (frontend_attributes|metadata)=\{[^{}]*(\{[^{}]*\}[^{}]*)*\}")


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(reduce_for_smoke(get_config("qwen1.5-0.5b")),
                              n_layers=2, kv_cache_dtype="int8",
                              quant=get_plan("w2a16"))
    params = lm.quantize_tree(lm.init_params(jax.random.PRNGKey(0), cfg), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def _step(served, which: str):
    """(step function, its arguments) of the prefill or of the first
    decode step after it."""
    cfg, params, tokens = served
    prefill = St.make_prefill_step(cfg, max_len=P + G)
    if which == "prefill":
        return prefill, (params, {"tokens": tokens})
    logits, caches = jax.jit(prefill)(params, {"tokens": tokens})
    batch = {"tokens": jnp.argmax(logits, -1).astype(jnp.int32),
             "pos": jnp.full((B,), P, jnp.int32)}
    return St.make_decode_step(cfg), (params, caches, batch)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (scan
    bodies, jitted calls), down to a Pallas call but not into its kernel."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name != "pallas_call":
            for sub in core.jaxprs_in_params(e.params):
                yield from _eqns(sub)


def canonical_hlo(text: str) -> list:
    """The instructions of an HLO module's text without attributes that
    name or locate (``frontend_attributes``, ``metadata``), and with every
    instruction and computation renamed by order of appearance."""
    out, names = [], {}

    def rename(m):
        return "%" + str(names.setdefault(m.group(1), len(names)))

    for line in text.splitlines():
        if not line.startswith((" ", "%", "ENTRY", "}", "HloModule")):
            continue                    # the stack-frame tables of metadata
        line = _ATTRS.sub("", line)
        if not line.startswith(" "):    # a computation: drop parameter names
            line = re.sub(r"([(,]\s*)[\w.\-]+: ", r"\1", line)
        out.append(re.sub(r"%([\w.\-]+)", rename, line))
    return out


def _unscoped(monkeypatch):
    """``obs.scope`` as a no-op; step functions made after this call are
    traced without names (made before it, jit would reuse their trace)."""
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_every_model_op_is_named(served, which):
    fn, args = _step(served, which)
    eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    names = [(e.ctx.xla_metadata or {}).get("scope") for e in eqns]
    stray = [(e.primitive.name, str(e.source_info.name_stack))
             for e, n in zip(eqns, names) if n not in NAMES]
    assert not stray
    assert set(names) == SEEN[which]
    assert SEEN["prefill"] | SEEN["decode"] == NAMES


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_names_leave_the_compiled_program_unchanged(served, which,
                                                    monkeypatch):
    fn, args = _step(served, which)
    named = jax.jit(fn).lower(*args).compile().as_text()
    assert set(re.findall(r'scope="([^"]*)"', named)) == SEEN[which]
    _unscoped(monkeypatch)
    fn, args = _step(served, which)
    plain = jax.jit(fn).lower(*args).compile().as_text()
    assert "scope=" not in plain
    assert canonical_hlo(named) == canonical_hlo(plain)


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_names_leave_logits_and_caches_bit_identical(served, which,
                                                     monkeypatch):
    fn, args = _step(served, which)
    named = jax.jit(fn)(*args)
    _unscoped(monkeypatch)
    fn, args = _step(served, which)
    plain = jax.jit(fn)(*args)
    flat_n, tree_n = jax.tree.flatten(named)
    flat_p, tree_p = jax.tree.flatten(plain)
    assert tree_n == tree_p
    for a, b in zip(flat_n, flat_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
