"""repro.obs — unified tracing + metrics for the whole mapping stack.

One dependency-free observability substrate (ISSUE 8), three parts:

- :mod:`repro.obs.trace` — request-scoped SPANS: thread-safe context
  managers over monotonic clocks, nested parent/child structure, one
  trace id per service request, propagated through every degradation
  rung, pipeline stage and backend call site.
- :mod:`repro.obs.metrics` — the process-wide telemetry REGISTRY:
  bounded named counters/gauges/histograms plus adapters absorbing the
  stack's scattered counters (compile caches, LRUs, services,
  breakers, faults) into one :func:`snapshot`.
- :mod:`repro.obs.export` — JSONL span logs (``REPRO_TRACE=path``),
  Chrome trace-event JSON for Perfetto, Prometheus text exposition,
  and an optional ``jax.profiler`` bridge (``REPRO_JAX_PROFILE=dir``).

This package imports only the stdlib; the instrumented modules import
it, never the reverse, so it is safe at the bottom of every layer.
"""

from .export import (JsonlSink, chrome_trace, install_env_sink,
                     jax_profile, prometheus_text, read_jsonl,
                     write_chrome_trace)
from .metrics import (REGISTRY, counter, gauge, instrument_compile_cache,
                      observe, register_cache, register_object,
                      register_provider, snapshot, span_rollup)
from .trace import (TRACER, Span, Tracer, add_sink, annotate, attach,
                    current_span, finished, format_tree, remove_sink,
                    reset, set_annotation, span, span_tree)

# arm the process-wide JSONL event log when REPRO_TRACE names a path
_ENV_SINK = install_env_sink()

__all__ = [
    "JsonlSink", "REGISTRY", "Span", "TRACER", "Tracer", "add_sink",
    "annotate", "attach", "chrome_trace", "counter", "current_span",
    "finished", "format_tree", "gauge", "install_env_sink",
    "instrument_compile_cache", "jax_profile", "observe",
    "prometheus_text", "read_jsonl", "register_cache",
    "register_object", "register_provider", "remove_sink", "reset",
    "set_annotation", "snapshot", "span", "span_rollup", "span_tree",
    "write_chrome_trace",
]
