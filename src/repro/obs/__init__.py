"""Unified telemetry for VSS: metrics registry, read-path tracing, and
exposition helpers.

- `MetricsRegistry` / `default_registry` — counters, gauges, fixed-
  bucket histograms; exact per-component handles summed into process-
  wide series (``registry.py``).
- `Tracer` / `Span` — per-`ReadSpec` span trees (``read`` → plan,
  fetch, decode with its per-GOP inflate/upload/download, admit),
  opened live around the work with wall and thread-CPU time, nested by
  an ambient current span (`Tracer.stage`), mirrored as
  ``vss.<name>`` ``jax.profiler`` annotations, and kept in a ring
  buffer (``trace.py``).
- `InstrumentedBackend` / `instrument_backend` — per-backend-kind op
  latency/bytes/error metrics, auto-applied by
  ``repro.storage.make_backend`` (``instrument.py``).
- ``python -m repro.obs.dump`` — offline snapshots of a live
  ``/metrics``+``/healthz`` endpoint or of this process' registry.

Set ``VSS_TELEMETRY=0`` to disable the default registry process-wide
(no-op handles, no instrumentation wrappers, no spans)."""

from repro.obs.registry import (
    ENV_TELEMETRY,
    LATENCY_BUCKETS,
    SIZE_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    LabelledCounter,
    MetricsRegistry,
    default_registry,
)
from repro.obs.trace import DEFAULT_TRACE_CAPACITY, Span, Tracer
from repro.obs.instrument import InstrumentedBackend, instrument_backend

__all__ = [
    "ENV_TELEMETRY",
    "LATENCY_BUCKETS",
    "SIZE_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "LabelledCounter",
    "MetricsRegistry",
    "default_registry",
    "DEFAULT_TRACE_CAPACITY",
    "Span",
    "Tracer",
    "InstrumentedBackend",
    "instrument_backend",
]
