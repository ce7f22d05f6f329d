"""repro.obs — the unified observability plane.

The paper's operational story (Fig. 4) is CloudWatch charts and alarms
over pipeline counters.  This plane reproduces the whole story and then
closes the loop the paper leaves to AWS:

  MetricsRegistry  typed Counter / Gauge / Histogram instruments with
                   labeled series, Prometheus text exposition, and a
                   json-safe snapshot()            (metrics.py)
  Tracer           trace_id/span() context managers with configurable
                   sampling, a bounded flight recorder, JSONL export,
                   and propagation on records — one document's journey
                   across ingest -> pipeline -> store -> delivery reads
                   back as one trace              (trace.py)
  StageProfiler    always-on per-stage wall-clock breakdown of the
                   batch-replay chain, each pass also a host event on
                   the profiler's timeline and an entry in a ring of
                   recent passes                 (profiler.py)
  kernel_launches  device launches per kernel and route (replay,
                   drain, query), with the new shapes each compiled;
                   beside it pack_slot_index, column packs per
                   slot-index path (dense, sort, session), and
                   pack_sessions, packed sessions per cut (key,
                   gap), and columnar_blocks_decoded, store blocks
                   read per dictionary layout (payload,
                   header)                        (launches.py)
  MetricsConnector self-monitoring: registry snapshots re-enter the
                   platform as an ordinary stream on a ``__health__``
                   channel, so the EXISTING rule engine alarms on the
                   platform itself               (selfmon.py)

``Observability`` bundles a registry + tracer for components that mount
the plane as one unit (``AlertMixPipeline`` builds one from
``PipelineConfig.trace_sample_rate`` / ``trace_export_dir``).

Import note: this package never imports ``repro.core`` / ``repro.store``
at module level (they import *us*); ``selfmon`` — which needs the
Connector data types — is imported lazily by its users.  Nor does it
import JAX: host events on the profiler's timeline are opened only once
something else has loaded JAX.
"""
from __future__ import annotations

from typing import Optional

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.latency import LatencySink, LatencyTracker
from repro.obs.launches import (columnar_blocks_decoded, kernel_launches,
                                pack_sessions, pack_slot_index)
from repro.obs.profiler import StageProfiler, recent_passes
from repro.obs.slo import SLOEngine, SLOSpec
from repro.obs.trace import Span, TraceExporter, Tracer, TracingSink


class Observability:
    """Registry + tracer, built as one unit from pipeline config."""

    def __init__(self, *, sample_rate: float = 0.0, trace_capacity: int = 4096,
                 export_dir: Optional[str] = None, seed: int = 0):
        exporter = TraceExporter(export_dir) if export_dir else None
        self.metrics = MetricsRegistry()
        self.tracer = Tracer(sample_rate=sample_rate,
                             capacity=trace_capacity, seed=seed,
                             exporter=exporter)

    def status(self) -> dict:
        return {"tracer": self.tracer.status(),
                "metrics": self.metrics.names()}

    def close(self) -> None:
        if self.tracer.exporter is not None:
            self.tracer.exporter.close()


__all__ = [
    "Counter", "Gauge", "Histogram", "LatencySink", "LatencyTracker",
    "MetricsRegistry", "Observability", "SLOEngine", "SLOSpec",
    "Span", "StageProfiler", "TraceExporter", "Tracer", "TracingSink",
    "columnar_blocks_decoded", "kernel_launches", "pack_sessions",
    "pack_slot_index", "recent_passes",
]
