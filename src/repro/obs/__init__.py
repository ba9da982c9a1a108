"""Observability: the flight recorder, the metrics plane, and the SLO
monitor (DESIGN.md §12).

Three small, dependency-free-inward pieces every other layer reports
into:

  * :mod:`repro.obs.recorder` — a process-global structured event/span
    tracer with a bounded ring, a monotonic clock, and a JSONL
    exporter, and a switchable sink that puts its spans in a JAX
    profiler trace; near-zero-overhead no-op when disabled.
  * :mod:`repro.obs.metrics` — named counters / gauges / streaming
    histograms (Welford + reservoir, the host twin of
    ``runtime.streamstats``) in a process-global registry.
  * :mod:`repro.obs.slo` — streaming tail-quantile-vs-target monitoring
    with multi-window burn-rate alarms, pluggable into the controller
    as a drift-alarm source.

``python -m repro.obs.report trace.jsonl`` renders a run timeline from
an exported trace (:mod:`repro.obs.report`).
"""
from .metrics import (Counter, Gauge, MetricsRegistry,  # noqa: F401
                      REGISTRY, StreamHist)
from .recorder import (EVENT_KINDS, Event, NULL_SPAN,  # noqa: F401
                       SPAN_NAMES, Recorder, active, event, install,
                       parse_jsonl, profile_spans, recording, span,
                       uninstall)
from .slo import SLOAlarm, SLOMonitor  # noqa: F401

__all__ = [
    "Counter", "EVENT_KINDS", "Event", "Gauge", "MetricsRegistry",
    "NULL_SPAN", "REGISTRY", "Recorder", "SLOAlarm", "SLOMonitor",
    "SPAN_NAMES", "StreamHist", "active", "event", "install",
    "parse_jsonl", "profile_spans", "recording", "span", "uninstall",
]
