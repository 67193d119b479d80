"""repro.obs — tracing, metrics, and predicted-vs-measured drift
monitoring (DESIGN.md §11).

Three parts: :mod:`~repro.obs.trace` (the per-process ring-buffer event
tracer every layer emits into, and the runtime :class:`~repro.obs.trace.span`
that also lands in the profiler's trace), :mod:`~repro.obs.export`
(Chrome-trace / Perfetto rendering with a netsim-predicted overlay), and
:mod:`~repro.obs.metrics` (counter/gauge registry snapshotting live
``TransportStats`` and published runtime records, plus drift gauges
against ``netsim.predict_*``).
"""

from . import trace
from .export import (
    parse_chrome_trace,
    sim_report_events,
    to_chrome_trace,
    write_chrome_trace,
)
from .metrics import REGISTRY, MetricsRegistry, get_registry
from .trace import Tracer, span

__all__ = [
    "trace",
    "Tracer",
    "span",
    "to_chrome_trace",
    "parse_chrome_trace",
    "write_chrome_trace",
    "sim_report_events",
    "MetricsRegistry",
    "REGISTRY",
    "get_registry",
]
