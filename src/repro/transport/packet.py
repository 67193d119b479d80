"""Packet transport: collectives over the dynamic router (DESIGN.md §3.2).

The flexibility path.  Every logical step — ring shift, explicit
permutation, routed p2p — is executed *end-to-end* by the store-and-forward
packet router of :mod:`repro.core.router`: payloads are packetised
(``pkt_elems`` f32 per packet + dst header), staged into the input FIFOs,
and the router runs enough cycles over the fixed physical link schedule to
deliver everything; arrivals are reassembled into the same arrays the
static backend would have produced.  Routing tables are runtime data, so
swapping the communicator's logical topology (torus → snake bus) re-routes
the exact same compiled collective — the paper's §5.3.1 experiment at the
collective level, not just for raw packets.

Delivery guarantees relied on for reassembly:

* each ``permute`` is a partial permutation (unique sources and unique
  destinations), so a receiver drains exactly one stream;
* packets of one stream follow one fixed route through FIFO queues, so
  they arrive in order;
* ``n_steps`` is a static worst-case bound (max hops + serialisation on
  the most contended link), so a lossless run delivers everything — the
  router's overflow counter *plus any delivery shortfall at the schedule's
  end* is accumulated into :attr:`Transport.stats` and equals 0 for every
  in-capacity run (asserted by tests).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import trace as obs
from .base import Transport, tree_bytes
from .registry import register_transport

# ------------------------------------------------------------------ wire


def _encode(leaf: jax.Array) -> jax.Array:
    """Leaf -> flat f32 wire vector, bit-exactly invertible for <=32-bit
    types (floats widen exactly; 32-bit ints ride as raw bits)."""
    assert leaf.dtype.itemsize <= 4, (
        f"packet wire format carries <=32-bit elements; got {leaf.dtype} "
        "(a 64-bit payload would silently truncate through the f32 wire)"
    )
    flat = leaf.reshape(-1)
    if leaf.dtype == jnp.float32:
        return flat
    if leaf.dtype in (jnp.int32, jnp.uint32):
        return lax.bitcast_convert_type(flat, jnp.float32)
    return flat.astype(jnp.float32)


def _decode(vec: jax.Array, shape, dtype) -> jax.Array:
    if dtype == jnp.float32:
        return vec.reshape(shape)
    if dtype in (jnp.int32, jnp.uint32):
        return lax.bitcast_convert_type(vec, dtype).reshape(shape)
    return vec.astype(dtype).reshape(shape)


# ------------------------------------------------------------- transport

#: router-table cache bound: the key includes the route table's bytes, so a
#: long-lived transport sweeping topologies would otherwise grow without
#: limit.  8 comfortably covers a working set of fabrics in flight.
TBL_CACHE_MAX = 8


def lru_get(cache: dict, key, make, cap: int = TBL_CACHE_MAX):
    """Tiny LRU on a plain (insertion-ordered) dict: hit moves the entry to
    the back; a miss past ``cap`` evicts the front (least recent)."""
    if key in cache:
        cache[key] = cache.pop(key)  # refresh recency
        return cache[key]
    while len(cache) >= max(int(cap), 1):
        cache.pop(next(iter(cache)))
    val = cache[key] = make()
    return val


@register_transport("packet")
@dataclass
class PacketTransport(Transport):
    """Store-and-forward packet router as a Transport backend.

    ``pkt_elems`` scales the paper's 28 B network packet to a TPU-friendly
    payload; ``slack_steps`` pads the static delivery-time bound (left at
    the default it simply costs a few bubble cycles).  ``router_impl``
    picks the router datapath (``core/router.py``: "scalar" | "vector" |
    "pallas"; None is "vector").
    """

    pkt_elems: int = 32
    slack_steps: int = 4
    #: override the computed worst-case transit queue depth (tests use a
    #: deliberately undersized queue to prove the overflow counter fires)
    transit_cap: int | None = None
    runtime_stats: bool = True
    router_impl: str | None = None
    _tbl_cache: dict = field(default_factory=dict, repr=False)

    # -- routing-table + schedule bounds (static, per communicator) ------

    def _phys_dims(self, comm) -> tuple[int, ...]:
        # The physical fabric is the torus implied by the mesh axes.
        return tuple(comm.axis_sizes)

    def _route_table(self, comm) -> jax.Array:
        from ..core.router import make_router_tables

        # key on the actual connection lists AND the route-table bytes —
        # two `from_edges` topologies share name="custom", and one link set
        # admits different route tables (DOR vs BFS tie-breaks)
        key = (
            comm.axis_sizes,
            comm.topology.links,
            comm.route_table.next_hop.tobytes(),
        )
        # derive from the communicator's own route table so the router
        # follows exactly the paths _bounds() analysed (a comm created
        # with routing_scheme="bfs" must not get fresh DOR routes)
        tbl = lru_get(self._tbl_cache, key, lambda: np.asarray(
            make_router_tables(
                comm.topology, self._phys_dims(comm), rt=comm.route_table
            )
        ))
        return jnp.asarray(tbl)

    def _bounds(self, comm, active_pairs, n_packets: int):
        """(n_steps, transit_cap): static worst-case delivery bounds.

        n_steps: longest route + full serialisation of the most contended
        directed link (each link moves one packet per cycle).
        transit_cap: most packets that can ever be parked at one rank.
        """
        edge_load: dict[tuple[int, int], int] = {}
        transit_load = np.zeros(comm.size, np.int64)
        max_hops = 1
        for s, d in active_pairs:
            path = comm.route_table.path(s, d)
            max_hops = max(max_hops, len(path) - 1)
            for a, b in zip(path[:-1], path[1:]):
                edge_load[(a, b)] = edge_load.get((a, b), 0) + 1
            for mid in path[1:-1]:
                transit_load[mid] += 1
        max_edge = max(edge_load.values(), default=1)
        n_steps = max_hops + n_packets * max_edge + self.slack_steps
        transit_cap = self.transit_cap
        if transit_cap is None:
            transit_cap = max(4, n_packets * int(transit_load.max()) + 2)
        return n_steps, transit_cap

    # ------------------------------------------------------------- steps

    def permute(self, x, comm, pairs):
        from ..core.router import RouterConfig, run_router

        n = comm.size
        pairs = [(int(s), int(d)) for s, d in pairs]
        active = [(s, d) for s, d in pairs if s != d]
        if not active:
            return x
        srcs = [s for s, _ in active]
        dsts = [d for _, d in active]
        assert len(set(srcs)) == len(srcs) and len(set(dsts)) == len(dsts), (
            "packet transport moves partial permutations: unique srcs/dsts "
            f"required, got {pairs}"
        )

        leaves, treedef = jax.tree.flatten(x)
        if not leaves:
            return x
        parts = [_encode(l) for l in leaves]
        vec = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        T = vec.size
        if T == 0:
            return x
        E = self.pkt_elems
        K = -(-T // E)  # packets per sender

        # Per-rank roles from the static pair list (SPMD: same trace
        # everywhere; the rank lookup selects the live role).
        r = comm.rank()
        dst_arr = np.full(n, -1, np.int32)
        for s, d in active:
            dst_arr[s] = d
        keep_arr = np.zeros(n, bool)  # (r, r) self-pairs: local delivery
        for s, d in pairs:
            if s == d:
                keep_arr[s] = True
        recv_arr = np.zeros(n, bool)
        for _, d in active:
            recv_arr[d] = True

        dst_r = jnp.asarray(dst_arr)[r]
        sends = dst_r >= 0
        pay = jnp.pad(vec, (0, K * E - T)).reshape(1, K, E)
        inq_dst = jnp.broadcast_to(
            jnp.clip(dst_r, 0, n - 1), (1, K)
        ).astype(jnp.int32)
        inq_len = jnp.where(sends, K, 0).astype(jnp.int32)[None]

        n_steps, transit_cap = self._bounds(comm, active, K)
        cfg = RouterConfig(
            dims=self._phys_dims(comm), n_ports=1, fifo_cap=K,
            transit_cap=transit_cap, out_cap=K, pkt_elems=E,
        )
        out_pay, out_cnt, ovf, _ = run_router(
            cfg, comm, self._route_table(comm), pay, inq_dst, inq_len,
            n_steps, impl=self.router_impl,
        )
        self._guard_runtime_reuse(ovf)
        self.tally(n_steps, tree_bytes(x))
        is_recv = jnp.asarray(recv_arr)[r]
        # Undelivered packets (an under-provisioned n_steps bound) would
        # silently back-fill zeros below — fold the delivery shortfall into
        # the loss counter so the tests' "overflow == 0" oracle catches it.
        shortfall = jnp.where(is_recv, K - out_cnt[0], 0).astype(jnp.int32)
        self.stats.add_overflow(ovf + shortfall)
        if obs.TRACING:
            # the counter itself is a traced runtime value; the event marks
            # where it accrues and carries the static schedule bounds
            obs.emit("router.overflow", tag=self._tag, n_steps=int(n_steps),
                     packets=int(K), transit_cap=int(transit_cap),
                     counter="stats.overflow")

        got = out_pay[0].reshape(K * E)[:T]
        keeps = jnp.asarray(keep_arr)[r]
        wire = jnp.where(is_recv, got, jnp.where(keeps, vec, 0.0))

        out_leaves, off = [], 0
        for l in leaves:
            out_leaves.append(_decode(wire[off:off + l.size], l.shape, l.dtype))
            off += l.size
        return jax.tree.unflatten(treedef, out_leaves)

    def p2p(self, x, *, src, dst, comm, n_chunks: int = 1):
        """Whole message as one packet train src -> dst through the router
        (``n_chunks`` is a scheduling hint other backends use; the router's
        chunking is its packet size)."""
        del n_chunks
        if src == dst:
            return x
        return self.permute(x, comm, [(src, dst)])


@register_transport("packet:pallas")
@dataclass
class PallasPacketTransport(PacketTransport):
    """The packet backend pinned to the Pallas tick kernel
    (``kernels/router``): the router's FIFO/arbiter state is updated in
    place inside one ``pallas_call`` per tick — a Mosaic kernel on TPU,
    the Pallas interpreter elsewhere (the equivalence tests drive it on
    CPU through this key).  Forward-only; the bare ``"packet"`` key runs
    the differentiable vector datapath everywhere."""

    router_impl: str | None = "pallas"
