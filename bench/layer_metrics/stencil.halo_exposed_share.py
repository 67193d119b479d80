"""Share of the traced window in which a halo collective runs on a device
and no other operation does, averaged over the cell's devices.

The halo slabs move as collective permutes (``channels/``, ``transport/``,
``apps/halo.py``); an asynchronous permute shows as a start and a done op,
and the done op lasts as long as the device waits for the slab.  A cell
without any collective (one rank) has nothing to read.
"""

from bench import trace_reduce as tr


def read(ctx):
    red = ctx.trace
    if not any(tr.COLLECTIVE.search(o.name) for d in red.devices for o in d.ops):
        return None
    exposed = sum(tr.exposed_ns(d, red.window) for d in red.devices)
    return 100.0 * exposed / len(red.devices) / red.window_ns
