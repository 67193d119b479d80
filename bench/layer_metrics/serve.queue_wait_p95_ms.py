"""95th percentile of the time a request waits for a slot: from when it was
due to the start of the engine tick that admitted it (the engine's
``admit_step`` counter, timed on the benchmark's clock), over every request
due in the window.  A request never admitted waits to the end of the run."""

import numpy as np


def read(ctx):
    wait = ctx.stats.get("queue_wait_s")
    if not wait:
        return None
    return 1e3 * float(np.percentile(np.asarray(wait, np.float64), 95))
