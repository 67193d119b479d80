"""Per-process ring-buffer event tracer (DESIGN.md §11).

One process holds at most one active :class:`Tracer`; producers all over the
stack — channel open/close/push/pop/transfer, the packet router's schedule
facts, the netsim autotuner's chosen plans, the fault-tolerance watchdog —
emit through the module-level :func:`emit` behind the :data:`TRACING` flag.

The disabled path is the design constraint: tracing is off by default and
instrumentation sits on trace-time hot paths (every channel push/pop call
site), so a disabled call site must cost one module-attribute load plus a
bool test and allocate *nothing*.  That is why call sites are written

    if trace.TRACING:
        trace.emit("channel.push", tag=..., port=...)

— the kwargs dict is only ever built when a tracer is live (asserted by
``tests/test_obs.py`` with tracemalloc).

Event schema (stable; the exporter embeds it verbatim):

    {"ts": float seconds since the tracer epoch,
     "rank": int | None          # None = host / SPMD trace-time event,
     "kind": str                 # dotted producer.verb, e.g. "channel.push",
     "tag":  str | None          # the ChannelSpec / TransportStats tag,
     "port": int | None          # the channel's claimed port,
     "attrs": dict}              # producer-specific payload (JSON-safe)

Timestamps are host ``perf_counter`` times.  SPMD producers emit once per
*python trace*, not per runtime step — a channel push event marks where the
schedule staged an element, not a runtime packet (runtime counters live in
``TransportStats`` and the metrics snapshot).

Runtime spans are the other kind of producer: :class:`span` wraps host work
that runs every step (the serving engine's tick phases).  A span always
enters a ``jax.profiler.TraceAnnotation``, so under the profiler it lands in
the ``.xplane.pb`` on the device trace's clock; with a tracer enabled it is
also recorded as one schema event whose ``attrs["dur"]`` the exporter
renders as a complete slice.

jax-free at import by design, so the netsim/tuner side can import it before
jax initialises; the profiler is imported on the first span.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager

#: the stable event schema's keys, in canonical order
EVENT_KEYS = ("ts", "rank", "kind", "tag", "port", "attrs")

#: fast-path flag mirroring ``_TRACER is not None``; call sites test this
#: before building any kwargs so the disabled path allocates nothing
TRACING = False

_TRACER: "Tracer | None" = None


class Tracer:
    """Bounded event recorder: a deque ring buffer of schema events.

    ``capacity`` bounds memory on long runs (oldest events fall off);
    ``clock`` is injectable for deterministic tests.  All timestamps are
    relative to the tracer's construction (``t0``), so exported traces
    start near zero.
    """

    __slots__ = ("capacity", "clock", "t0", "_events")

    def __init__(self, capacity: int = 65536, clock=time.perf_counter):
        self.capacity = int(capacity)
        self.clock = clock
        self.t0 = clock()
        self._events = deque(maxlen=self.capacity)

    def now(self) -> float:
        """Seconds since the tracer epoch (the event ``ts`` base)."""
        return self.clock() - self.t0

    def event(self, kind: str, *, rank=None, tag=None, port=None,
              ts=None, **attrs):
        """Record one schema event.  ``ts=None`` stamps :meth:`now`;
        extra keyword arguments become the event's ``attrs`` payload."""
        self._events.append({
            "ts": self.now() if ts is None else float(ts),
            "rank": rank,
            "kind": kind,
            "tag": tag,
            "port": port,
            "attrs": attrs,
        })

    def events(self) -> list:
        """Snapshot of the buffer, oldest first."""
        return list(self._events)

    def kinds(self) -> set:
        return {e["kind"] for e in self._events}

    def clear(self):
        self._events.clear()

    def __len__(self) -> int:
        return len(self._events)


def enable(capacity: int = 65536, clock=time.perf_counter) -> Tracer:
    """Install (and return) a fresh process-wide tracer."""
    global _TRACER, TRACING
    _TRACER = Tracer(capacity, clock)
    TRACING = True
    return _TRACER


def disable() -> "Tracer | None":
    """Remove the active tracer (returns it, with its events intact)."""
    global _TRACER, TRACING
    t, _TRACER, TRACING = _TRACER, None, False
    return t


def get() -> "Tracer | None":
    return _TRACER


def emit(kind: str, **kw):
    """Record an event on the active tracer; no-op when tracing is off.

    Hot call sites must still guard with ``if trace.TRACING:`` *before*
    building ``kw`` — this function is the slow-path funnel, the flag test
    is the fast path."""
    t = _TRACER
    if t is not None:
        t.event(kind, **kw)


@contextmanager
def enabled(capacity: int = 65536, clock=time.perf_counter):
    """Scoped tracing: install a fresh tracer, restore the previous one
    (usually none) on exit.  Yields the tracer — its events stay readable
    after the block."""
    global _TRACER, TRACING
    prev = _TRACER
    t = enable(capacity, clock)
    try:
        yield t
    finally:
        _TRACER = prev
        TRACING = prev is not None


#: ``jax.profiler.TraceAnnotation``, imported on the first span
_ANNOTATION = None


class span:
    """Runtime span: ``with span("serve.admit"): ...``.

    ``name`` is a dotted ``layer.phase``; ``ids`` (a request's ``uid``, a
    slot) go into the profiler annotation and the schema event's ``attrs``.
    With the profiler off the annotation costs about a microsecond; with no
    tracer enabled nothing else is recorded.
    """

    __slots__ = ("name", "ids", "_ann", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self._t0 = None

    def __enter__(self):
        global _ANNOTATION
        if _ANNOTATION is None:
            from jax.profiler import TraceAnnotation

            _ANNOTATION = TraceAnnotation
        self._ann = _ANNOTATION(self.name, **self.ids)
        self._ann.__enter__()
        if _TRACER is not None:
            self._t0 = _TRACER.now()
        return self

    def __exit__(self, *exc):
        t = _TRACER
        if t is not None and self._t0 is not None:
            t.event(self.name, ts=self._t0, dur=t.now() - self._t0, **self.ids)
        self._ann.__exit__(*exc)
        return False
