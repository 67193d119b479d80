"""95th percentile, over the requests due in the window, of the engine's
time from a request's admission to its first token, from the engine's own
record: the host time of the engine's ticks from the stamp of admission
(the tick that gave the request a slot) to the stamp of its first token on
the host.

Time between ticks is left out: it is the benchmark's own loop, and in a
traced run also the profiler's stop, which the window's end runs inside
the loop (about 10 s on a v5e, PERF.md §6), so it would otherwise land in
every prefill that spans the window's end.  The engine replays a prompt one
token a tick, so this is the prompt's length in ticks; with the queue wait
(``serve.queue_wait_p95_ms``) it makes up the time to first token.

The requests due in the window are the first ones the measured engine was
given: requests are submitted in due order, and ``queue_wait_s`` holds one
wait for each request due in the window.  One without a first token is
left out (it counts as failed).
"""

import numpy as np

from bench import engine_record

PHASES = ("admit_ns", "prepare_ns", "dispatch_ns", "sample_ns", "harvest_ns")


def read(ctx):
    snap = engine_record.snapshot()
    if snap is None or not snap["ticks"]["start_ns"]:
        return None
    due = snap["requests"][:len(ctx.stats.get("queue_wait_s", ()))]
    got = [(r["admit"], r["first"]) for r in due
           if r["admit"] is not None and r["first"] is not None]
    if not got:
        return None
    t = snap["ticks"]
    start = np.asarray(t["start_ns"], np.int64)
    dur = sum(np.asarray(t[k], np.int64) for k in PHASES)
    before = np.concatenate([[0], np.cumsum(dur)[:-1]])

    def engine_ns(stamp):
        k = np.searchsorted(start, stamp, side="right") - 1
        return before[k] + np.minimum(stamp - start[k], dur[k])

    admit, first = (np.asarray(x, np.int64) for x in zip(*got))
    ns = engine_ns(first) - engine_ns(admit)
    return 1e-6 * float(np.percentile(ns.astype(np.float64), 95))
