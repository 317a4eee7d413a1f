"""``repro.obs`` — pipeline observability: span tracing + metrics registry.

* :mod:`repro.obs.trace` — thread-tracked span tracer with zero-cost
  disabled paths and Chrome trace-event / Perfetto JSON export;
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` consolidating the
  per-tier ``*Stats`` dataclasses behind one ``snapshot()`` protocol,
  plus derived pipeline-level metrics (overlap, stall attribution,
  bytes-and-seconds rollup);
* :mod:`repro.obs.validate` — structural trace validation (also a CLI:
  ``python -m repro.obs.validate trace.json``);
* :mod:`repro.obs.phases` — the train step's device phases (its
  ``jax.named_scope`` names) and the map from compiled instructions to
  them that an enabled tracer records.

This package intentionally imports nothing from the rest of ``repro`` (no
jax, no numpy): every pipeline tier can depend on it without layering
cycles, and a disabled tracer costs one flag check per span.
"""

from repro.obs.metrics import MetricsRegistry, harvest, pipeline_rollup
from repro.obs.trace import (
    NULL_SPAN,
    Tracer,
    enable_tracing,
    get_tracer,
    set_tracer,
)
from repro.obs.validate import (
    TraceError,
    overlap_seconds,
    span_intervals,
    validate_trace,
)

__all__ = [
    "MetricsRegistry",
    "harvest",
    "pipeline_rollup",
    "NULL_SPAN",
    "Tracer",
    "enable_tracing",
    "get_tracer",
    "set_tracer",
    "TraceError",
    "overlap_seconds",
    "span_intervals",
    "validate_trace",
]
