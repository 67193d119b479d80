"""jit'd wrapper for the stencil sweep: padding + dispatch + time loop."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import pad_to, resolve_use_pallas
from .kernel import block_rows, stencil_pallas
from .ref import stencil_ref


@partial(jax.jit, static_argnames=("block_m", "use_pallas", "interpret"))
def stencil_step(
    x: jax.Array,
    *,
    block_m: int | None = None,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One sweep of the 4-point stencil with zero (Dirichlet) boundaries.

    ``block_m`` rows per kernel grid step (a multiple of 8); None sizes the
    slab from the row width so it fits VMEM (:func:`block_rows`)."""
    if not resolve_use_pallas(use_pallas) and not interpret:
        return stencil_ref(x)
    M, N = x.shape
    if block_m is None:
        block_m = block_rows(M, N)
    xp, _ = pad_to(x, block_m, 0)
    out = stencil_pallas(xp, block_m=block_m, interpret=interpret)
    # Zero-padded rows double as the zero Dirichlet boundary: row M-1's south
    # neighbour is xp[M] == 0, exactly the oracle's condition; rows >= M are
    # garbage and sliced off.
    return out[:M]


def stencil_run(x, n_steps: int, **kw):
    """n_steps sweeps (the paper's T timesteps)."""
    def body(_, v):
        return stencil_step(v, **kw)

    return jax.lax.fori_loop(0, n_steps, body, x)


def stencil_interior(x: jax.Array, **kw) -> jax.Array:
    """Interior output points of one sweep: rows/cols ``1..-2`` of
    :func:`stencil_step`, which depend only on values already resident in
    the local tile — no halo reads.  This is the compute the ``repro/apps``
    distributed stencil runs *while* its halo slabs are in flight (the
    overlap window); the boundary ring is finished after the exchange
    lands.  Same kwargs as :func:`stencil_step` (``use_pallas`` /
    ``interpret`` select the Pallas kernel), and bit-identical to the
    corresponding interior of the halo'd reference sweep: every point is
    the same ``0.25 * (n + s + w + e)`` f32 expression.
    """
    return stencil_step(x, **kw)[1:-1, 1:-1]
