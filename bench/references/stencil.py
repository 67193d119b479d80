"""The 4-point Jacobi stencil, swept on one rank over the whole domain."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def sweep(x):
    """One timestep with a zero boundary outside the domain:
    ``0.25 * (north + south + west + east)``, added in that order."""
    p = jnp.pad(x, 1)
    return (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]) \
        * jnp.asarray(0.25, x.dtype)


@partial(jax.jit, static_argnames=("steps", "dtype"))
def run(world, steps: int, dtype=jnp.float32):
    """``steps`` timesteps of ``world`` computed in ``dtype``; float32 is
    the configuration's precision, bfloat16 the control's."""
    x = jnp.asarray(world).astype(dtype)
    return jax.lax.fori_loop(0, steps, lambda _, v: sweep(v), x) \
        .astype(jnp.float32)


def assemble(tiles, grid):
    """(RX * RY, nx, ny) row-major tiles -> the (RX * nx, RY * ny) domain."""
    RX, RY = grid
    n, nx, ny = tiles.shape
    return tiles.reshape(RX, RY, nx, ny).transpose(0, 2, 1, 3) \
        .reshape(RX * nx, RY * ny)


def split(world, grid):
    """Inverse of :func:`assemble`."""
    RX, RY = grid
    X, Y = world.shape
    nx, ny = X // RX, Y // RY
    return world.reshape(RX, nx, RY, ny).transpose(0, 2, 1, 3) \
        .reshape(RX * RY, nx, ny)
