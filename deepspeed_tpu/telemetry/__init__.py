"""Unified telemetry: metrics registry + span tracing.

One process-local :class:`MetricsRegistry` (labeled counters, gauges,
fixed-bucket histograms) feeds every consumer from the same series:

  * ``render_prometheus()`` — Prometheus text exposition a serving
    deployment scrapes,
  * ``snapshot()`` — machine-readable JSON snapshot (benchmarks,
    dashboards, tests),
  * :class:`TelemetryBridge` — periodic flush of registry scalars into
    the ``MonitorMaster`` backends (TensorBoard/W&B/CSV).

Span tracing (``with trace.span("decode_step"):``) records wall-clock
spans into a ring buffer and can mirror them into ``jax.profiler`` trace
annotations (see :mod:`deepspeed_tpu.telemetry.trace`).

Both stacks are instrumented: the training engine (step/loss/grad-norm/
loss-scale + comms bytes) and inference v2 (TTFT, decode tokens/s, queue
depth, KV-pool utilization, preemptions, prefix-cache hits, speculative
accepts). See docs/TELEMETRY.md for the metrics catalog.
"""

from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       get_registry, render_federated, scoped_registry,
                       set_registry)
from .bridge import TelemetryBridge
from . import anomaly, collector, context, memory, postmortem, recorder, \
    timeline, trace, watchdog
from .anomaly import DiagnosticsConfig
from .context import TraceContext
from .recorder import FlightRecorder, get_recorder, set_recorder

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_registry", "set_registry", "scoped_registry",
    "render_federated", "TelemetryBridge", "trace", "timeline",
    "watchdog", "memory", "collector", "recorder", "anomaly", "postmortem",
    "context",
    "TraceContext", "DiagnosticsConfig", "FlightRecorder",
    "get_recorder", "set_recorder",
]
