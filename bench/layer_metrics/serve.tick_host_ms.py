"""Mean host time of an engine tick outside its ``sample`` phase, over
every tick of the measured engine that ran a decode step, from the
engine's own record: admission, building the step's inputs, dispatching
the step, and harvesting its tokens.

During ``sample`` the host waits for the chip; the rest of the tick is
host work in which the chip runs little or nothing, so this is the
engine's share of the device's gap between two decode steps (the serve
benchmark's loop adds its own).
"""

import numpy as np

from bench import engine_record


#: the record's phases other than ``sample``
HOST_PHASES = ("admit_ns", "prepare_ns", "dispatch_ns", "harvest_ns")


def read(ctx):
    snap = engine_record.snapshot()
    if snap is None or not snap["ticks"]["start_ns"]:
        return None
    t = snap["ticks"]
    host = sum(np.asarray(t[k], np.float64) for k in HOST_PHASES)
    return 1e-6 * float(np.mean(host))
