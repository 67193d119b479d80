"""Pipeline parallelism over SMI streaming channels.

The paper's GESUMMV application (§5.4.1) is MPMD functional decomposition:
rank 0 computes GEMV and *streams* results into rank 1's AXPY, doubling
aggregate memory bandwidth.  Generalised, that is pipeline parallelism:
each rank owns one stage's parameters, and activations stream stage-to-stage
through transient channels while every stage computes on its current
microbatch — a GPipe schedule where the inter-stage transfer is an SMI
channel hop (one ppermute along the stage chain).

The forward pass is written with ``lax.scan`` so ``jax.grad`` transposes the
schedule into the reverse pipeline automatically (ppermute transposes to the
reversed permutation), giving fwd+bwd pipelining without bespoke backward
scheduling code.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from .comm import Communicator
from .streaming import _mask_sel, _pvary


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_mb: jax.Array,
    comm: Communicator,
):
    """Run ``stage_fn`` as a P-stage pipeline over microbatches.

    stage_fn(params, x) -> y, with x and y the same shape (homogeneous
    stages, e.g. transformer blocks).
    stage_params: this rank's stage parameters (rank r = stage r).
    x_mb: (M, mb, ...) microbatches; logically resident at stage 0 (SPMD:
    every rank passes the same buffer, only stage 0 reads it).

    Returns (M, mb, ...): the last stage's outputs (zeros elsewhere).
    Schedule: M + P - 1 ticks; at tick t stage s computes microbatch t - s;
    activations hop one stage per tick through a persistent chain channel
    (tag ``"pp.stage"``), opened once for the whole schedule.
    """
    from ..parallel import ledger
    from ..parallel.layers import stage_transport
    from ..transport.base import tree_bytes

    P = comm.size
    r = comm.rank()
    M = x_mb.shape[0]
    chain = [(i, i + 1) for i in range(P - 1)]
    spec, t = stage_transport(comm)

    def tick(carry, tk):
        out, buf = carry
        m = tk - r  # microbatch index at this stage
        active = jnp.logical_and(m >= 0, m < M)
        # stage 0 reads its microbatch from the feed; others read the channel
        feed = lax.dynamic_index_in_dim(x_mb, jnp.clip(tk, 0, M - 1), 0, keepdims=False)
        inp = _mask_sel(r == 0, feed, buf)
        y = stage_fn(stage_params, inp)
        y = _mask_sel(active, y, _pvary(jnp.zeros_like(y), comm))
        # deliver at the last stage
        upd = lax.dynamic_update_index_in_dim(out, y, jnp.clip(m, 0, M - 1), 0)
        out = _mask_sel(jnp.logical_and(r == P - 1, active), upd, out)
        # stream to the next stage (one chain-channel hop)
        with t.tagged(spec.stats_tag):
            buf = t.permute(y, comm, chain) if P > 1 else y
        return (out, buf), None

    y_shape = jax.eval_shape(stage_fn, stage_params, x_mb[0])
    out0 = _pvary(jnp.zeros((M,) + y_shape.shape, y_shape.dtype), comm)
    buf0 = _pvary(jnp.zeros(y_shape.shape, y_shape.dtype), comm)
    (out, _), _ = lax.scan(tick, (out0, buf0), jnp.arange(M + P - 1))
    if P > 1:
        # the scan body traces once but the schedule hops every tick;
        # account the full hop count (stage_transport leaves the instance
        # un-attached so the body's single trace-time tally isn't mirrored)
        nb = tree_bytes(y_shape)
        ledger.tally(spec.stats_tag, M + P - 1, nb * (M + P - 1))
    return out


def pipeline_loss(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x_mb: jax.Array,
    y_mb: jax.Array,
    comm: Communicator,
):
    """Pipelined forward + per-microbatch loss at the last stage.

    loss_fn(pred, target) -> scalar.  Returns the mean loss (valid on the
    last stage; psum'd so every stage sees it).  Differentiable: jax.grad
    w.r.t. ``stage_params`` yields this rank's stage gradient, with the
    reverse pipeline schedule generated by AD.
    """
    P = comm.size
    r = comm.rank()
    out = pipeline_apply(stage_fn, stage_params, x_mb, comm)
    M = x_mb.shape[0]
    per_mb = jax.vmap(loss_fn)(out, y_mb)  # (M,)
    local = jnp.where(r == P - 1, per_mb.mean(), 0.0)
    return lax.psum(local, comm.axis)
