"""Device time of each layer of the model step, from the layer names the
program puts on its operations (``repro.obs.scope``).

The program sets the XLA frontend attribute ``scope`` on every operation
its model code emits (``embed``, ``layers``, ``norm``, ``attn/qkv``,
``attn/kv_write``, ``attn/core``, ``attn/out``, ``mlp/up``, ``mlp/down``,
``lm_head``, ``cache_fold``). XLA keeps it on the instructions it fuses and
on the Pallas kernels' custom calls, and the profiler prints it in each
``XLA Ops`` event's HLO text: ``..., frontend_attributes={scope="attn/core"}``.
An operation that XLA inserts itself, such as a layout or loop-carry
``copy``, carries none; its time is ``UNSCOPED``.

A layer's time is the self time (``trace_reduce.Reduced.ops``) of the
operations of one executable that carry its name, over the traced steps
of that executable: the layers and ``UNSCOPED`` partition the executable's
operation time. A program that names nothing, such as one built before the
names existed, reads None.
"""

from __future__ import annotations

import re

UNSCOPED = "unscoped"
# the attribute, inside the frontend attributes (which may hold nested
# groups, such as a Pallas call's ``kernel_metadata={}``)
_SCOPE = re.compile(
    r'frontend_attributes=\{(?:[^{}]|\{[^{}]*\})*?\bscope="([^"]*)"')


def scope_of(hlo_text: str) -> str:
    """The layer name of an ``XLA Ops`` event's HLO text, or UNSCOPED."""
    m = _SCOPE.search(hlo_text)
    return m.group(1) if m else UNSCOPED


def split(trace, module: str) -> dict | None:
    """{layer name or UNSCOPED: self seconds} of the operations of the
    executables whose base name matches the regex ``module``; None when
    none of them carries a name."""
    out: dict[str, float] = {}
    for key, (seconds, _) in trace.ops.items():
        if re.search(module, key.split("/", 1)[0]):
            name = scope_of(trace.texts[key])
            out[name] = out.get(name, 0.0) + seconds
    return out if set(out) - {UNSCOPED} else None


def _ms(run, module: str, counter: str, name: str):
    if run.trace is None or not run.counters[counter]:
        return None
    parts = split(run.trace, module)
    if parts is None:
        return None
    return 1e3 * parts.get(name, 0.0) / run.counters[counter]


def decode_ms(run, name: str):
    """Device time of layer ``name`` (or UNSCOPED) in the decode
    executable per decode step, in ms."""
    return _ms(run, run.decode_module, "decode_steps", name)


def prefill_ms(run, name: str):
    """Device time of layer ``name`` (or UNSCOPED) in the prefill
    executable per prefill step, in ms."""
    return _ms(run, run.prefill_module, "prefill_steps", name)
