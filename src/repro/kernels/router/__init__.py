"""Vectorized router datapath kernels (DESIGN.md §10).

``ref`` holds the pure single-tick datapath (absorb + arbitrate) of the
lax "vector" implementation; ``kernel`` restates the same tick in ops
Mosaic lowers, as one ``pallas_call`` whose FIFO/arbiter state stays
aliased in place across ticks.
"""

from .kernel import router_tick_pallas  # noqa: F401
from .ref import TickSpec, router_absorb, router_tick, tick_spec_of  # noqa: F401
