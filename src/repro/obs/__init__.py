"""repro.obs — serving-stack observability.

Instrumentation for the serving engine, the kernel registry and the model
step:

  metrics   unified labeled counters/gauges/histograms with a scoped
            registry stack (MetricsRegistry, scoped, global_registry,
            record_kernel_dispatch, percentile)
  trace     per-request lifecycle spans + engine step-phase timeline with
            an injectable clock, exportable as JSONL and Chrome-trace JSON
            (Tracer, FakeClock)
  scope     layer names on the device trace: ``scope(name)`` around model
            code tags its compiled operations (``jax.named_scope`` and the
            XLA frontend attribute ``scope``)

``metrics`` and ``trace`` import no jax; ``scope`` imports it when used.

See docs/observability.md for metric names, the span schema, and how to
open the exported traces in Perfetto.
"""

from . import metrics  # noqa: F401
from .metrics import (MetricsRegistry, global_registry,  # noqa: F401
                      percentile, record_kernel_dispatch, scoped, summarize)
from .scope import scope  # noqa: F401
from .trace import FakeClock, Span, Tracer  # noqa: F401
