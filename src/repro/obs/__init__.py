"""Telemetry: metrics registry, exporters, CI gate.

Host-side only by construction (DESIGN.md §9): hooks run *around* jitted
programs — at python trace time or between device calls — so enabling
telemetry never changes lowered HLO or served tokens, and disabling it
leaves one branch on the hot path.  Spans are the JAX profiler's own
(``jax.profiler.TraceAnnotation`` in ``serve/engine.py``), on the clock
of the device trace.
"""
from .metrics import (REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
                      enabled, flatten_snapshot, get_registry, set_enabled,
                      write_snapshot)

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "enabled", "set_enabled", "get_registry", "flatten_snapshot",
    "write_snapshot",
]
