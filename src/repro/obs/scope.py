"""Layer names on the device trace.

``scope(name)`` names every operation traced inside it, for the two readers
of a compiled program:

- ``jax.named_scope(name)`` prefixes the ops' ``op_name`` metadata, which
  is what an operator sees in xprof;
- ``set_xla_metadata(scope=name)`` sets the XLA frontend attribute
  ``scope``, which XLA keeps on the fused instructions and the profiler
  prints in each ``XLA Ops`` event's HLO text
  (``frontend_attributes={scope="attn/core"}``); the benchmark splits
  device time by it.

Names are flat, full strings (``attn/kv_write``, not ``kv_write`` under
``attn``); the innermost scope wins. They are compile-time metadata: a
scope adds no host work per step and changes no instruction.

``host_span(name)`` is the host-side counterpart: a
``jax.profiler.TraceAnnotation`` that puts a span of the host's work on
the profiler's clock, beside the device's operations.

jax is imported where it is used, so that importing ``repro.obs`` (and
its jax-free ``metrics`` and ``trace``) imports no jax.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def scope(name: str):
    """Name the operations traced inside the block ``name``."""
    import jax
    from jax.experimental.xla_metadata import set_xla_metadata

    with jax.named_scope(name), set_xla_metadata(scope=name):
        yield


def host_span(name: str):
    """A span ``name`` of the host's work on the profiler's trace (a
    context manager; records nothing while no profiler runs)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)
