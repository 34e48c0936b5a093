"""Observability layer: trace rings, phase spans, reports (DESIGN.md §16).

Three pieces, layered:

* ``obs.trace``  — on-device trace rings + the ``RunTrace`` record every
  engine attaches as ``ColoringResult.trace`` when called with
  ``trace=True`` (a STATIC knob: ``trace=False`` compiles the identical
  XLA program and stays bit-identical/zero-cost).
* ``obs.spans``  — host-side phase spans, always recorded into a bounded
  process-wide ring, stamped on the profiler's clock (``time.time_ns()``)
  and shown in profiler traces as ``repro.<name>`` annotations, with the
  compiles and cache loads JAX reports counted per phase;
  ``last_requests(k)`` reads the last ``k`` ``repro.color`` requests.
* ``obs.report`` — the shared text reporter
  (``python -m repro.obs.report``).

A profiler trace (``jax.profiler.trace``) is the timeline view: it holds
the ``repro.*`` spans beside the device's programs and ops.
"""
from .report import format_metrics, format_result, format_spans, format_trace
from .spans import SpanEvent, SpanRecorder, last_requests, recorder, span
from .trace import (
    DEFAULT_TRACE_CAP,
    NF,
    TRACE_FIELDS,
    HostRing,
    RunTrace,
    assemble_trace,
    empty_trace,
    resolve_trace_cap,
    ring_init,
    ring_rows,
)

__all__ = [
    "TRACE_FIELDS",
    "NF",
    "DEFAULT_TRACE_CAP",
    "HostRing",
    "RunTrace",
    "assemble_trace",
    "empty_trace",
    "resolve_trace_cap",
    "ring_init",
    "ring_rows",
    "SpanEvent",
    "SpanRecorder",
    "recorder",
    "span",
    "last_requests",
    "format_result",
    "format_trace",
    "format_spans",
    "format_metrics",
]
