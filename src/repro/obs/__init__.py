"""Structured tracing & metrics for the engine and serving stack.

Imports nothing from ``repro`` (and nothing outside the standard library
but JAX's profiler binding, lazily), so every layer — engine stages, the
disk cache, serve lanes, the batcher — can reach the ambient tracer
without import cycles. See ``obs/tracer.py`` for the model: spans +
retrospective events + a counters registry, exported as Chrome
trace-event JSON (Perfetto / chrome://tracing) and as the
``stage_timings_us`` / ``counters`` blocks stamped into records and run
metadata (schema v8). While a ``jax.profiler`` session records, every
live span is also a profiler span with its attributes as stats, on the
device trace's clock — with or without an in-process :class:`Tracer`.
"""

from repro.obs.tracer import (
    NULL_TRACER,
    PROFILER,
    Counters,
    NullTracer,
    SpanEvent,
    Tracer,
    collection_spans,
    current_tracer,
    set_tracer,
    use_tracer,
)

__all__ = [
    "NULL_TRACER",
    "PROFILER",
    "Counters",
    "NullTracer",
    "SpanEvent",
    "Tracer",
    "collection_spans",
    "current_tracer",
    "set_tracer",
    "use_tracer",
]
