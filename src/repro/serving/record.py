"""What the continuous engine did, request by request and tick by tick.

The engine keeps one :class:`EngineRecord` beside its ``admit_step`` and
``finish_step`` tick counters and publishes it in
``repro.obs.metrics.REGISTRY`` under the engine's name, where a reader
finds it after the engine is gone.  It holds host integers only, no device
array, and it is bounded (:data:`CAPACITY`): the oldest requests and
ticks fall off.

Every stamp is ``time.time_ns()``, the clock the profiler's trace is based
on (an xplane event's ``profile_start_time + start_ns``), so a stamp can be
placed on the device trace.

* Per request (by ``uid``): ``submit``, ``admit`` (it took a slot),
  ``first`` and ``finish`` (its first and last token reached the host).
* Per tick that ran a decode step: its start and the host time of each
  phase of :data:`PHASES`, in order, plus the JIT compilations begun and
  the garbage-collector pause inside it
  (:func:`repro.obs.metrics.runtime_counters`), and the prompt tokens it
  took into the cache: in its prompt chunk (``prefill_tokens``) and
  through decode rows, one a slot (``replay_tokens``).
"""

from __future__ import annotations

from collections import OrderedDict, deque

#: the most requests and ticks a record keeps; older ones fall off
CAPACITY = 1 << 16

#: the phases of a tick, in the order they run; ``sample`` is the one in
#: which the host waits for the chip
PHASES = ("admit", "prepare", "dispatch", "sample", "harvest")

#: a request's stamps, in the order they happen
STAMPS = ("submit", "admit", "first", "finish")


class EngineRecord:
    """Bounded request and tick record of one engine (see the module)."""

    def __init__(self):
        self._requests: OrderedDict = OrderedDict()  # uid -> [stamps]
        self._ticks: deque = deque(maxlen=CAPACITY)

    def stamp(self, uid, which: str, ns: int) -> None:
        """Set request ``uid``'s stamp ``which`` (one of :data:`STAMPS`)."""
        r = self._requests.get(uid)
        if r is None:
            r = self._requests[uid] = [None] * len(STAMPS)
            if len(self._requests) > CAPACITY:
                self._requests.popitem(last=False)
        r[STAMPS.index(which)] = ns

    def tick(self, bounds, compiles: int, gc_ns: int, prefill_tokens: int,
             replay_tokens: int) -> None:
        """One tick: ``bounds`` are the host times at its start and at the
        end of each phase (``len(PHASES) + 1`` stamps)."""
        self._ticks.append((*bounds, compiles, gc_ns, prefill_tokens,
                            replay_tokens))

    def __len__(self) -> int:
        return len(self._ticks)

    def snapshot(self) -> dict:
        """JSON-safe copy: ``requests`` as a list of ``{"uid", *STAMPS}``
        and ``ticks`` as columns, ``start_ns``, one ``<phase>_ns``
        duration per phase, ``compiles``, ``gc_ns``, ``prefill_tokens``
        and ``replay_tokens``."""
        n = len(PHASES) + 1
        ticks = {"start_ns": [t[0] for t in self._ticks]}
        for k, phase in enumerate(PHASES):
            ticks[f"{phase}_ns"] = [t[k + 1] - t[k] for t in self._ticks]
        ticks["compiles"] = [t[n] for t in self._ticks]
        ticks["gc_ns"] = [t[n + 1] for t in self._ticks]
        ticks["prefill_tokens"] = [t[n + 2] for t in self._ticks]
        ticks["replay_tokens"] = [t[n + 3] for t in self._ticks]
        return {
            "requests": [{"uid": uid, **dict(zip(STAMPS, r))}
                         for uid, r in self._requests.items()],
            "ticks": ticks,
        }
