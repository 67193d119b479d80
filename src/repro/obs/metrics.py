"""Counter/gauge registry + live TransportStats snapshots (DESIGN.md §11).

The benchmark drivers register every live transport backend under a name
(:meth:`MetricsRegistry.track`); :meth:`MetricsRegistry.snapshot` then
renders the registry into one JSON-safe dict — counters, gauges, and the
full :class:`~repro.transport.base.TransportStats` of each tracked backend
including its ``by_tag`` splits and the packet router's overflow counter.
Snapshots read the *live* stats objects, so the numbers are exactly the
trace-time counters the netsim predictions are asserted against
(``tests/test_obs.py`` checks equality to the byte).

Live records are the runtime side: an object with a ``snapshot()`` that
keeps what happened at run time, published under a name
(:meth:`MetricsRegistry.publish`; the serving engine's request and tick
record).  :func:`runtime_counters` holds the process-wide counts no single
call reports: JIT compilations begun and Python GC pause time.

Drift gauges turn the bench-only ``--validate-sim`` 2x gate into a
continuously-sampled metric: :meth:`MetricsRegistry.drift` records the
symmetric prediction ratio ``max(pred/meas, meas/pred)`` — computed by the
same :func:`repro.netsim.calibrate.drift_ratio` helper ``validate`` gates
on, so the gauge and the gate can never disagree — and
:meth:`MetricsRegistry.drift_from_records` samples a whole calibration-
record set, returning the worst ratio (== ``validate``'s).
"""

from __future__ import annotations

import gc
import time


def _num(x):
    """Best-effort concrete number for a counter that may hold a traced
    jax value (the packet router's overflow inside an open trace): int
    when concrete, None when unavailable."""
    if x is None:
        return None
    try:
        return int(x)
    except Exception:  # a (dead) tracer from a jitted run: not concrete
        return None


class MetricsRegistry:
    """Process-level metric store: monotonic counters, point-in-time
    gauges, and live transport references snapshotted on demand."""

    def __init__(self):
        self.counters: dict = {}
        self.gauges: dict = {}
        self._transports: dict = {}  # name -> live Transport
        self.records: dict = {}      # name -> live record (has snapshot())

    # ---------------------------------------------------------- writers

    def inc(self, name: str, delta=1):
        self.counters[name] = self.counters.get(name, 0) + delta

    def gauge(self, name: str, value: float):
        self.gauges[name] = float(value)

    def track(self, name: str, transport):
        """Register a live transport; its stats are read at snapshot time
        (re-tracking a name replaces the previous instance)."""
        self._transports[name] = transport

    def publish(self, name: str, record):
        """Register a live record; its ``snapshot()`` is read at snapshot
        time (publishing a name again replaces the previous record)."""
        self.records[name] = record

    # ------------------------------------------------------------ drift

    def drift(self, name: str, *, predicted: float, measured: float) -> float:
        """Record ``drift/<name>`` = the symmetric prediction ratio (the
        ``--validate-sim`` gate's quantity; 1.0 = perfect)."""
        from ..netsim.calibrate import drift_ratio

        ratio = drift_ratio(predicted, measured)
        self.gauge(f"drift/{name}", ratio)
        return ratio

    def drift_from_records(self, label: str, records, *, model) -> float:
        """Sample drift gauges from netsim calibration records under a
        fitted :class:`~repro.netsim.model.LinkModel`: one gauge per
        record (``drift/<label>/<name>``) plus the worst ratio under
        ``drift/<label>`` — by construction the exact worst ratio
        :func:`repro.netsim.calibrate.validate` computes for the same
        records and model."""
        worst = 1.0
        for i, r in enumerate(records):
            ratio = self.drift(
                f"{label}/{r.get('name') or i}",
                predicted=model.predict(r), measured=r["seconds"],
            )
            worst = max(worst, ratio)
        self.gauge(f"drift/{label}", worst)
        return worst

    # --------------------------------------------------------- snapshot

    @staticmethod
    def stats_dict(stats) -> dict:
        """One TransportStats as a JSON-safe dict (the snapshot's per-
        transport payload; by_tag is copied, overflow concretised when
        possible — a traced counter from a jitted run reads as None)."""
        return {
            "steps": int(stats.steps),
            "bytes": int(stats.bytes_moved),
            "overflow": _num(stats.overflow),
            "by_tag": {
                tag: {"steps": int(e["steps"]), "bytes": int(e["bytes"])}
                for tag, e in stats.by_tag.items()
            },
        }

    def snapshot(self) -> dict:
        """The whole registry as one JSON-safe dict (``records`` only where
        a record is published)."""
        snap = {
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "transports": {
                name: {"name": getattr(t, "name", "") or type(t).__name__,
                       **self.stats_dict(t.stats)}
                for name, t in self._transports.items()
            },
        }
        if self.records:
            snap["records"] = {name: r.snapshot()
                               for name, r in self.records.items()}
        return snap

    def clear(self):
        self.counters.clear()
        self.gauges.clear()
        self._transports.clear()
        self.records.clear()


#: the process-default registry the benchmark drivers write into
REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return REGISTRY


# ---------------------------------------------------------------------------
# process-wide runtime counters
# ---------------------------------------------------------------------------

#: ``jax.monitoring`` events that mark a compilation begun: a jit cache miss
#: traces its function, then compiles it (or reads it from the persistent
#: cache) for the backend
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
})


class RuntimeCounters:
    """Monotonic process-wide counts: ``compiles`` begun (jaxpr traces and
    backend compiles, from ``jax.monitoring``'s start-of-event scalar),
    and ``gc_ns``, the host time Python's garbage collector paused the
    process (``gc.callbacks``).  A reader takes the difference of two
    readings."""

    def __init__(self):
        self.compiles = 0
        self.gc_ns = 0
        self._gc_t0 = None

    def install(self):
        import jax.monitoring

        jax.monitoring.register_scalar_listener(self._on_scalar)
        gc.callbacks.append(self._on_gc)

    def _on_scalar(self, event, value, **kw):
        if event in COMPILE_EVENTS:
            self.compiles += 1

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_t0 is not None:
            self.gc_ns += time.perf_counter_ns() - self._gc_t0
            self._gc_t0 = None


_RUNTIME: "RuntimeCounters | None" = None


def runtime_counters() -> RuntimeCounters:
    """The process's :class:`RuntimeCounters`, its listeners installed on
    the first call."""
    global _RUNTIME
    if _RUNTIME is None:
        _RUNTIME = RuntimeCounters()
        _RUNTIME.install()
    return _RUNTIME
