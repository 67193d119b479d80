"""The serving engine's own record, for the per-layer metrics that read it.

The program keeps it in memory: each ``ContinuousEngine`` publishes its
request and tick record in ``repro.obs.metrics.REGISTRY.records`` under its
name, and a new engine of that name replaces the old one, so after a run
the record is the measured engine's and not the warm-up's.  Its stamps and
phase times are host ``time.time_ns()`` integers (PERF.md §3).  A program
that publishes no record gives None.
"""

#: the name the serve cell's engines publish under
ENGINE = "serve"


def snapshot() -> dict | None:
    """The record's ``snapshot()``: ``requests``, a list of ``{"uid",
    "submit", "admit", "first", "finish"}`` stamps (None where the event
    has not happened), and ``ticks``, columns ``start_ns``, one
    ``<phase>_ns`` per phase, ``compiles`` and ``gc_ns``."""
    try:
        from repro.obs.metrics import REGISTRY
    except ImportError:
        return None
    rec = getattr(REGISTRY, "records", {}).get(ENGINE)
    return None if rec is None else rec.snapshot()
