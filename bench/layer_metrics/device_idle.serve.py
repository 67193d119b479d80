"""Share of the traced window in which no operation ran on a device,
averaged over the cell's devices: 1 - busy union / window."""

from bench import trace_reduce as tr


def read(ctx):
    return 100.0 * tr.idle_share(ctx.trace)
