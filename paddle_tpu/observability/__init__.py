"""paddle_tpu.observability — runtime telemetry (round 15).

Two halves, one import surface:

- :mod:`.metrics` — the structured metrics registry: labeled
  Counter/Gauge/Histogram families with a near-zero-cost disabled path,
  thread-safe mutation (the async serving engine's dispatch/reconcile
  split, the watchdog monitor thread), and ``snapshot()`` /
  ``snapshot_flat()`` export — the schema-checked ``telemetry``
  sub-object riding the bench JSON lines.
- :mod:`.tracing` — host spans + per-request async lanes recorded into
  the profiler's event buffer and exported through
  ``profiler.export_chrome_tracing``; ``monotonic()``/``monotonic_ns()``
  are THE timing clock for ``inference/`` and ``distributed/`` (tpulint
  AL006 fences raw ``time.perf_counter()`` there to this layer);
  ``step_scope()`` names the parts of the two step programs on the device
  (``STEP_SCOPES``, read by ``benchmark/scope_trace.py``); ``phase()``
  records set-up, always, into :mod:`.startup`.
- :mod:`.startup` (PR 38) — set-up: the always-on ``process_registry``
  and ``setup_record``, fed by ``phase()`` and by a listener on jax's
  trace / lower / compile spans and persistent-cache events, registered
  when this package is imported.

Cost contract: with observability disabled (no profiler window open,
``default_registry`` off) every instrument call is one flag check and an
immediate return — the churn-smoke bench gates the end-to-end overhead
(see ARCHITECTURE.md round 15).
"""
from .fleet import FleetInstruments
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_registry, disable_metrics, enable_metrics,
                      merge_snapshots, metrics_enabled)
from .startup import process_registry, setup_record
from .tracing import (REQUEST_SPAN, STEP_SCOPES, STEP_SUBSCOPES,
                      device_annotation,
                      monotonic, monotonic_ns, phase, request_begin,
                      request_end, request_event, span, step_scope,
                      tracing_active)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "enable_metrics", "disable_metrics", "metrics_enabled",
    "merge_snapshots", "span", "phase", "process_registry", "setup_record",
    "request_begin", "request_event",
    "request_end", "tracing_active", "monotonic",
    "monotonic_ns", "device_annotation", "REQUEST_SPAN", "STEP_SCOPES",
    "STEP_SUBSCOPES",
    "step_scope", "FleetInstruments",
]
