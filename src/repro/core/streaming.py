"""Transient channels and the chunk-pipelined point-to-point engine (paper §3.1).

The paper's key primitive is the *transient channel*: open(count, dtype, peer,
port, comm) then Push/Pop one element per clock cycle inside the pipelined
loop, with the transport layer forwarding packets hop-by-hop.

The channel API itself lives in :mod:`repro.channels` — ``open_channel`` /
``push`` / ``pop`` / ``Channel.transfer`` plus the transient collective
channels — and is re-exported here for the historic import paths.  What
remains in this module:

* :func:`stream_p2p` — the legacy transfer-level entry point, now a thin
  shim that opens a transient (anonymous-port) p2p channel and streams the
  message through it.  Its ``transport=`` / ``plan=`` kwargs keep working
  but are deprecated: open a channel carrying the config instead
  (DESIGN.md §9 has the migration table).
* :func:`stream_exchange` — single-hop bulk exchange over explicit pairs
  (the halo-exchange wire; `repro.apps` drives it through a ChannelSpec).
* the shard_map harness helpers used across tests and benchmarks.

Everything here must execute *inside* ``jax.shard_map`` spanning the
communicator's mesh axes.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from ..compat import make_mesh as _compat_make_mesh
from ..compat import pvary_missing
from .comm import Communicator


def _mask_sel(pred, a, b):
    """where() with scalar pred broadcast over pytrees of equal shape."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _pvary(x, comm: "Communicator"):
    """Mark freshly-created constants as device-varying over the comm axes.

    shard_map's varying-manual-axes type system requires loop carries that
    flow through ppermute to be 'varying'; zeros created inside the region
    start out 'invariant'."""
    names = tuple(comm.axis_names)
    return jax.tree.map(lambda v: pvary_missing(v, names), x)


pvary = _pvary  # public: mark user loop-carry state varying over comm axes


# ---------------------------------------------------------------------------
# Transfer-level streaming p2p (transient-channel shim)
# ---------------------------------------------------------------------------


def stream_p2p(
    x: jax.Array,
    *,
    src: int,
    dst: int,
    comm: Communicator,
    n_chunks: int = 1,
    transport=None,
    plan=None,
) -> jax.Array:
    """Stream ``x`` (resident on ``src``) to ``dst`` along the routed path.

    Every rank passes a same-shaped ``x`` (SPMD); only the source's content
    is transmitted.  Returns a buffer that equals ``x``@src on ``dst`` and is
    zeros elsewhere.

    This is a compatibility shim over the channel API: it opens a transient
    anonymous-port p2p channel carrying the call's config and streams the
    message with :meth:`~repro.channels.Channel.transfer` — the static/fused
    backends run the chunk-pipelined multi-hop ppermute schedule
    (``n_chunks`` chunks in flight, the asynchronicity degree k of §3.3);
    the packet backend stages the message into the dynamic router.

    ``transport=`` and ``plan=`` are deprecated here: carry them on the
    channel instead (``open_channel(comm, src=..., dst=...,
    transport=..., plan=...)``), where they configure *every* transfer and
    push/pop of the channel, not one call.
    """
    from ..channels import open_channel

    if transport is not None or plan is not None:
        warnings.warn(
            "stream_p2p(transport=..., plan=...) is deprecated; open a "
            "channel carrying the config instead: open_channel(comm, "
            "src=..., dst=..., transport=..., plan=...).transfer(x) "
            "(DESIGN.md §9)",
            DeprecationWarning,
            stacklevel=2,
        )
    ch = open_channel(
        comm, src=src, dst=dst, port=None, transport=transport, plan=plan
    )
    return ch.transfer(x, n_chunks=n_chunks)


def stream_exchange(
    x: jax.Array,
    *,
    pairs: list[tuple[int, int]],
    comm: Communicator,
    transport=None,
    tag: str | None = None,
) -> jax.Array:
    """Single-hop bulk exchange over explicit (src, dst) pairs — the
    "fixed wiring" streaming model of paper Fig. 3, for benchmarks and halo
    exchanges between mesh neighbours (one physical link per pair).

    ``tag`` buckets the step's wire accounting under a message tag
    (:meth:`repro.transport.base.Transport.tagged`), so application phases
    sharing a backend instance keep separable cost counters."""
    from ..transport.registry import resolve_transport

    t = resolve_transport(transport, comm)
    if tag is None:
        return t.permute(x, comm, pairs)
    with t.tagged(tag):
        return t.permute(x, comm, pairs)


# ---------------------------------------------------------------------------
# Element-level transient channels: re-exported from repro.channels
# ---------------------------------------------------------------------------

#: names served lazily from repro.channels (PEP 562) — a top-level import
#: here would cycle (channels -> core.comm -> core package -> this module)
_CHANNEL_EXPORTS = (
    "Channel",
    "ChannelSpec",
    "channel_transfer",
    "open_channel",
    "pop",
    "push",
)


def __getattr__(name):
    if name in _CHANNEL_EXPORTS:
        from .. import channels

        return getattr(channels, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "Channel",
    "ChannelSpec",
    "channel_transfer",
    "open_channel",
    "pop",
    "push",
    "pvary",
    "stream_exchange",
    "stream_p2p",
    "run_spmd",
    "make_test_mesh",
]


# ---------------------------------------------------------------------------
# shard_map harness helpers (used by tests/examples/benchmarks)
# ---------------------------------------------------------------------------


def run_spmd(fn, mesh, in_specs, out_specs, *args):
    """jit(shard_map(fn)) one-liner used across tests and benchmarks."""
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )(*args)


def make_test_mesh(shape, names):
    """Host-device mesh with Auto axis types (tests / benchmarks)."""
    return _compat_make_mesh(shape, names)
