"""Dynamic packet-switched transport (paper §4.2–§4.3).

The paper's transport layer: CK_S/CK_R kernels connected to the physical
QSFP links, forwarding fixed-size network packets according to routing
tables that are *uploaded at runtime* — topology or rank-count changes
never rebuild the bitstream.

TPU rendering (DESIGN.md §2): the compiled XLA executable is the bitstream.
It executes a **fixed** per-step link schedule — one ppermute per physical
link id (±1 along each mesh dim, the ICI torus wiring) — and the routing
table is a runtime ``(n, n)`` int32 array mapping (rank, dst) -> link id.
Swapping tables re-routes the same compiled program, reproducing the paper's
flexibility experiment (torus vs. bus without rebuild) exactly.

Per router step (one "clock cycle"):
  1. per link: arbitrate a packet whose table entry routes it out that link
     — transit traffic first (drain the network), then input-FIFO traffic
     with the paper's R-stickiness polling (§4.3: keep reading the same
     FIFO up to R times before moving on);
  2. all links fire their ppermute (invalid packets ride as bubbles);
  3. arrivals are delivered (dst == me: pushed to the port's output buffer)
     or parked in the transit FIFO for the next hop.

Store-and-forward with a bounded transit FIFO; an overflow counter is
returned so tests/benchmarks can assert lossless runs (the paper's links
provide backpressure; we provide provable-capacity schedules instead).
A delivery buffer past ``out_cap`` and a transit queue past ``transit_cap``
both *drop* the packet and count it in ``overflow``.

Packets: payload (PKT_ELEMS f32) + header (dst rank, port) — the 28 B + 4 B
network packet of §4.2, scaled to a TPU-friendly chunk.

Three implementations of the identical tick semantics (DESIGN.md §10):

* ``impl="scalar"`` — the per-link Python-unrolled reference loop;
* ``impl="vector"`` — whole-state array ops (one masked argmax arbitrates
  all links per tick, prefix-sum absorb), ONE packed ``all_to_all``
  exchange per tick instead of a ppermute per link, and an early-exit
  batched tick loop (a scan of cond'd batches — reverse-differentiable)
  that goes idle as soon as the network drains;
* ``impl="pallas"`` — the vector tick as a Pallas kernel
  (``kernels/router``) whose FIFO/arbiter state is aliased in place:
  compiled by Mosaic on TPU, run by the Pallas interpreter elsewhere.  It
  is forward-only (a ``pallas_call`` has no transpose).

``impl=None`` is "vector" on every platform: the training path
differentiates through the router, which the kernel cannot.  All three
produce bit-identical ``(out_pay, out_cnt, overflow, t_done)`` — asserted
by the equivalence tests in ``tests/test_router.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..compat import pvary_missing, vma_of
from ..obs import trace as obs
from .comm import Communicator
from .routing import compute_route_table, physical_link_map
from .streaming import _pvary
from .topology import Topology

LOCAL = -1  # routing-table value for "deliver here" (never looked up)


def make_links(dims: tuple[int, ...]):
    """Physical link list for a torus mesh: (link_id, perm pairs).

    link 2*i   = +1 along dim i; link 2*i+1 = -1 along dim i (omitted when
    the dim has size <= 2, where -1 == +1)."""
    topo = Topology.torus(dims)
    n = topo.n_ranks
    strides = []
    s = 1
    for d in reversed(dims):
        strides.append(s)
        s *= d
    strides = list(reversed(strides))

    def coords(r):
        return [(r // strides[i]) % dims[i] for i in range(len(dims))]

    def rank_of(c):
        return sum(c[j] * strides[j] for j in range(len(dims)))

    links = []
    for i, d in enumerate(dims):
        if d == 1:
            continue
        steps = (+1,) if d == 2 else (+1, -1)
        for sidx, step in enumerate(steps):
            pairs = []
            for r in range(n):
                c = coords(r)
                c[i] = (c[i] + step) % d
                pairs.append((r, rank_of(c)))
            links.append((2 * i + sidx, pairs))
    return links


def make_router_tables(
    topology: Topology, dims: tuple[int, ...], rt=None
) -> np.ndarray:
    """The route generator for the dynamic router: (n, n) int32 of link ids.

    Every edge of ``topology`` must be a physical neighbour pair on the
    ``dims`` torus (the paper's constraint: logical connections are real
    wires).  Entry [r, d] = physical link id of the first hop r -> d.
    Pass ``rt`` (a precomputed RouteTable, e.g. a communicator's) to make
    the router follow exactly those paths instead of recomputing with the
    default scheme."""
    if rt is None:
        rt = compute_route_table(topology)
    phys = physical_link_map(dims)
    # remap ids for size-2 dims where only the +1 link exists
    links = make_links(dims)
    live_ids = {lid for lid, _ in links}

    def canon(lid):
        return lid if lid in live_ids else lid - 1  # -1 of a size-2 dim -> +1

    n = topology.n_ranks
    tbl = np.full((n, n), LOCAL, dtype=np.int32)
    for r in range(n):
        for d in range(n):
            if r == d:
                continue
            nh = int(rt.next_hop[r, d])
            assert (r, nh) in phys, (
                f"logical edge {r}->{nh} of {topology.name} is not a physical "
                f"link on torus{dims}; embed the topology first (e.g. snake_bus)"
            )
            tbl[r, d] = canon(phys[(r, nh)])
    return tbl


def snake_bus(dims: tuple[int, int]) -> Topology:
    """A linear bus embedded in the torus along a boustrophedon path — the
    paper's 'treat the 8 FPGAs as a linear bus by editing the connection
    list' experiment (§5.3.1)."""
    X, Y = dims
    order = []
    for x in range(X):
        ys = range(Y) if x % 2 == 0 else range(Y - 1, -1, -1)
        order += [x * Y + y for y in ys]
    edges = list(zip(order[:-1], order[1:]))
    t = Topology.from_edges(X * Y, edges, name=f"snake_bus{dims}")
    return t


@dataclass(frozen=True)
class RouterConfig:
    dims: tuple[int, ...]
    n_ports: int = 2          # application endpoints per rank
    fifo_cap: int = 8         # input FIFO depth (paper: compile-time buffer)
    transit_cap: int = 16     # CK transit queue depth
    out_cap: int = 16         # delivery buffer per port
    pkt_elems: int = 32       # payload elements (the 28 B packet, scaled)
    R: int = 8                # polling stickiness (paper §4.3)
    switch_bubble: bool = False  # model the FPGA CK's sequential polling
    # cost: switching input FIFOs costs one dead cycle on the link (the
    # paper's Tab. 4 effect; our combinational arbiter has no such cost
    # physically, so it is opt-in for the reproduction benchmark)
    tick_batch: int = 4  # ticks advanced per loop body in the
    # vector/pallas datapath; the drain check (one psum of the pending
    # count) runs once per batch, so up to tick_batch - 1 idle (identity)
    # ticks run past the drain point


def _exchange_tables(links, n: int):
    """Static per-rank exchange tables for the packed all_to_all tick.

    ``nbr[r, li]`` = the rank link ``li`` delivers to from ``r``;
    ``src[r, li]`` = the rank whose link-``li`` packet lands on ``r``.
    ``packed_ok`` is True when every rank's link destinations are distinct
    (always the case for torus links), so one (n, F) row buffer carries at
    most one packet per destination and a single tiled ``all_to_all``
    replaces the per-link ppermutes."""
    NL = len(links)
    nbr = np.zeros((n, NL), np.int32)
    src = np.zeros((n, NL), np.int32)
    for li, (_lid, pairs) in enumerate(links):
        for s, d in pairs:
            nbr[s, li] = d
            src[d, li] = s
    packed_ok = all(len(set(nbr[q])) == NL for q in range(n))
    return nbr, src, packed_ok


def run_router(
    cfg: RouterConfig,
    comm: Communicator,
    route_tbl: jax.Array,      # (n, n) int32 link ids — RUNTIME data
    inq_pay: jax.Array,        # (n_ports, fifo_cap, E) staged messages
    inq_dst: jax.Array,        # (n_ports, fifo_cap) destination ranks
    inq_len: jax.Array,        # (n_ports,) packets staged per FIFO
    n_steps: int,
    *,
    impl: str | None = None,
):
    """Execute up to ``n_steps`` router cycles.  Must run inside shard_map.

    Returns (out_pay, out_cnt, overflow, t_done): per-port delivery
    buffers, their fill counts, the loss counter (0 == lossless run) and
    the last delivery tick.  ``impl`` picks the datapath ("scalar" |
    "vector" | "pallas"; None is "vector" — see module docstring); the
    vector/pallas datapaths may stop early once the network drains, which
    never changes the returned values.
    """
    links = make_links(cfg.dims)
    if impl is None:
        impl = "vector"
    if impl != "scalar" and (not links or inq_pay.dtype != jnp.float32):
        # degenerate fabrics (no links) and exotic wire dtypes keep the
        # reference path; the packetised wire is always f32
        impl = "scalar"
    if obs.TRACING:
        obs.emit("router.run", impl=impl, n_steps=int(n_steps),
                 n_links=len(links), n_ports=int(cfg.n_ports),
                 dims=list(cfg.dims))
    if impl == "scalar":
        return _run_router_scalar(
            cfg, comm, route_tbl, inq_pay, inq_dst, inq_len, n_steps, links)
    assert impl in ("vector", "pallas"), impl
    return _run_router_vector(
        cfg, comm, route_tbl, inq_pay, inq_dst, inq_len, n_steps, links,
        use_pallas=impl == "pallas")


def _run_router_scalar(
    cfg, comm, route_tbl, inq_pay, inq_dst, inq_len, n_steps, links
):
    """The per-link scalar reference loop (the equivalence-test oracle)."""
    n = comm.size
    r = comm.rank()
    E = cfg.pkt_elems
    NP = cfg.n_ports
    NL = len(links)
    my_tbl = route_tbl[jnp.minimum(r, n - 1)]  # (n,) link id per dst

    def init():
        z = lambda *sh_dt: _pvary(jnp.zeros(*sh_dt), comm)
        return dict(
            inq_head=z((NP,), jnp.int32),
            inq_len=_pvary(inq_len.astype(jnp.int32), comm),
            tr_pay=z((cfg.transit_cap, E), inq_pay.dtype),
            tr_dst=z((cfg.transit_cap,), jnp.int32),
            tr_port=z((cfg.transit_cap,), jnp.int32),
            tr_head=z((), jnp.int32),
            tr_cnt=z((), jnp.int32),
            out_pay=z((NP, cfg.out_cap, E), inq_pay.dtype),
            out_cnt=z((NP,), jnp.int32),
            overflow=z((), jnp.int32),
            last_src=z((NL,), jnp.int32),
            stick=z((NL,), jnp.int32),
            t_done=z((), jnp.int32),
        )

    def fifo_head(st, p):
        """Head packet of input FIFO p: (pay, dst, port, has)."""
        h = st["inq_head"][p]
        pay = inq_pay[p, jnp.minimum(h, cfg.fifo_cap - 1)]
        dst = inq_dst[p, jnp.minimum(h, cfg.fifo_cap - 1)]
        has = h < st["inq_len"][p]
        return pay, dst, p, has

    def transit_head(st):
        h = st["tr_head"] % cfg.transit_cap
        return st["tr_pay"][h], st["tr_dst"][h], st["tr_port"][h], st["tr_cnt"] > 0

    def step(t, st):
        # ---- gather candidate heads: sources 0..NP-1 = FIFOs, NP = transit
        pays, dsts, ports, has_l = [], [], [], []
        for p in range(NP):
            pay, dst, port, has = fifo_head(st, p)
            pays.append(pay); dsts.append(dst); ports.append(jnp.asarray(port)); has_l.append(has)
        tpay, tdst, tport, thas = transit_head(st)
        pays.append(tpay); dsts.append(tdst); ports.append(tport); has_l.append(thas)
        pays = jnp.stack(pays)               # (S, E)
        dsts = jnp.stack(dsts)               # (S,)
        ports = jnp.stack([jnp.asarray(p, jnp.int32) for p in ports])
        has = jnp.stack(has_l)                  # (S,)
        S = NP + 1
        want_link = jnp.where(dsts == r, -2, my_tbl[jnp.clip(dsts, 0, n - 1)])  # (S,)

        taken = jnp.zeros((S,), bool)
        sel_src = []
        for li, (lid, _) in enumerate(links):
            avail = jnp.logical_and(has, jnp.logical_and(want_link == lid, ~taken))
            # transit priority: if transit wants this link, take it.
            tr_want = avail[S - 1]
            # R-stickiness round-robin over FIFO sources
            last = st["last_src"][li]
            stickok = st["stick"][li] < cfg.R
            keep = jnp.logical_and(stickok, avail[jnp.clip(last, 0, S - 1)])
            # next available after `last` (rotate & argmax)
            idxs = (last + 1 + jnp.arange(S)) % S
            rot = avail[idxs]
            off = jnp.argmax(rot)
            rr = idxs[off]
            chosen = jnp.where(tr_want, S - 1, jnp.where(keep, last, rr))
            any_avail = avail.any()
            if cfg.switch_bubble:
                # sequential-polling model: acquiring a new FIFO burns the
                # cycle (the link sends nothing) but the arbiter latches on
                switching = jnp.logical_and(any_avail, chosen != last)
                send = jnp.logical_and(any_avail, ~switching)
            else:
                send = any_avail
            new_last = jnp.where(any_avail, chosen, last)
            new_stick = jnp.where(
                jnp.logical_and(send, chosen == last), st["stick"][li] + 1, 0
            )
            st["last_src"] = st["last_src"].at[li].set(new_last)
            st["stick"] = st["stick"].at[li].set(new_stick)
            chosen = jnp.where(send, chosen, -1)
            taken = jnp.where(send, taken.at[jnp.clip(chosen, 0, S - 1)].set(True), taken)
            sel_src.append(chosen)

        # ---- pop selected sources
        for li in range(NL):
            c = sel_src[li]
            for p in range(NP):
                hit = c == p
                st["inq_head"] = st["inq_head"].at[p].add(jnp.where(hit, 1, 0))
            hit_tr = c == S - 1
            st["tr_head"] = st["tr_head"] + jnp.where(hit_tr, 1, 0)
            st["tr_cnt"] = st["tr_cnt"] - jnp.where(hit_tr, 1, 0)

        # ---- fire all links (fixed wiring; bubbles ride as invalid)
        arrivals = []
        for li, (lid, pairs) in enumerate(links):
            c = sel_src[li]
            val = c >= 0
            cs = jnp.clip(c, 0, S - 1)
            pay = pays[cs]
            dst = jnp.where(val, dsts[cs], -1)
            prt = jnp.where(val, ports[cs], 0)
            pay, dst, prt, val = jax.tree.map(
                lambda v: lax.ppermute(v, comm.axis, pairs), (pay, dst, prt, val)
            )
            arrivals.append((pay, dst, prt, val))

        # ---- absorb arrivals: deliver or park in transit
        for pay, dst, prt, val in arrivals:
            mine = jnp.logical_and(val, dst == r)
            fwd = jnp.logical_and(val, dst != r)
            # deliver to port buffer; a full buffer drops the packet and
            # counts it in overflow, like a transit overrun (it must not
            # silently overwrite the last delivered packet)
            fits = st["out_cnt"][jnp.clip(prt, 0, NP - 1)] < cfg.out_cap
            delivered = jnp.logical_and(mine, fits)
            for p in range(NP):
                hit = jnp.logical_and(delivered, prt == p)
                slot = jnp.clip(st["out_cnt"][p], 0, cfg.out_cap - 1)
                newbuf = st["out_pay"].at[p, slot].set(pay)
                st["out_pay"] = jnp.where(hit, newbuf, st["out_pay"])
                st["out_cnt"] = st["out_cnt"].at[p].add(jnp.where(hit, 1, 0))
            st["overflow"] = st["overflow"] + jnp.where(
                jnp.logical_and(mine, ~fits), 1, 0
            )
            st["t_done"] = jnp.where(delivered, t.astype(jnp.int32), st["t_done"])
            # park in transit ring buffer
            room = st["tr_cnt"] < cfg.transit_cap
            ok = jnp.logical_and(fwd, room)
            tail = (st["tr_head"] + st["tr_cnt"]) % cfg.transit_cap
            st["tr_pay"] = jnp.where(ok, st["tr_pay"].at[tail].set(pay), st["tr_pay"])
            st["tr_dst"] = jnp.where(ok, st["tr_dst"].at[tail].set(dst), st["tr_dst"])
            st["tr_port"] = jnp.where(ok, st["tr_port"].at[tail].set(prt), st["tr_port"])
            st["tr_cnt"] = st["tr_cnt"] + jnp.where(ok, 1, 0)
            st["overflow"] = st["overflow"] + jnp.where(
                jnp.logical_and(fwd, ~room), 1, 0
            )
        return st

    st = lax.fori_loop(0, n_steps, step, init())
    return st["out_pay"], st["out_cnt"], st["overflow"], st["t_done"]


def _run_router_vector(
    cfg, comm, route_tbl, inq_pay, inq_dst, inq_len, n_steps, links, *,
    use_pallas: bool,
):
    """Vectorized batched-tick datapath (DESIGN.md §10).

    Per tick: ``router_tick`` (absorb + one-shot arbitration, pure array
    ops — or the Pallas kernel wrapping the same function) followed by ONE
    packed ``all_to_all`` moving every link's packet row.  The tick loop
    is a ``scan`` of ``cond``'d batches advancing ``cfg.tick_batch`` ticks
    each that go idle as soon as the psum'd pending count reports the
    network drained — idle ticks are identity
    on every returned value, so the early out is output-invariant with
    the scalar reference running all ``n_steps`` cycles, and scan+cond
    keep the datapath reverse-differentiable for the training path.
    """
    from ..kernels.common import on_tpu
    from ..kernels.router import router_absorb, router_tick, \
        router_tick_pallas, tick_spec_of

    n = comm.size
    r = comm.rank()
    E = cfg.pkt_elems
    NL = len(links)
    F = E + 3  # lanes: dst, port, valid + payload
    spec = tick_spec_of(cfg, n, [lid for lid, _ in links])
    my_tbl = route_tbl[jnp.minimum(r, n - 1)]
    inq_len = inq_len.astype(jnp.int32)
    nbr, src, packed_ok = _exchange_tables(links, n)
    nbr_r = jnp.asarray(nbr)[jnp.minimum(r, n - 1)]
    src_r = jnp.asarray(src)[jnp.minimum(r, n - 1)]

    # the state varies over the comm axes and over every axis an operand
    # varies over (a router on "model" inside a (data, model) mesh); the
    # drain count is psum'd over the comm axes only
    vma = frozenset(comm.axis_names).union(
        *(vma_of(a) for a in (route_tbl, inq_pay, inq_dst, inq_len)))
    live0 = pvary_missing(jnp.asarray(1, jnp.int32),
                          tuple(vma - frozenset(comm.axis_names)))

    def init():
        z = lambda *sh_dt: pvary_missing(jnp.zeros(*sh_dt), tuple(vma))
        st = dict(
            inq_head=z((cfg.n_ports,), jnp.int32),
            tr_pay=z((cfg.transit_cap, E), inq_pay.dtype),
            tr_dst=z((cfg.transit_cap,), jnp.int32),
            tr_port=z((cfg.transit_cap,), jnp.int32),
            tr_head=z((), jnp.int32),
            tr_cnt=z((), jnp.int32),
            out_pay=z((cfg.n_ports, cfg.out_cap, E), inq_pay.dtype),
            out_cnt=z((cfg.n_ports,), jnp.int32),
            overflow=z((), jnp.int32),
            last_src=z((NL,), jnp.int32),
            stick=z((NL,), jnp.int32),
            t_done=z((), jnp.int32),
        )
        arr = (z((NL, E), inq_pay.dtype), z((NL,), jnp.int32),
               z((NL,), jnp.int32), z((NL,), bool))
        return st, arr

    def tick(st, arr, t):
        if use_pallas:
            return router_tick_pallas(
                spec, my_tbl, inq_pay, inq_dst, inq_len, st, *arr, r, t,
                interpret=not on_tpu())
        return router_tick(
            spec, my_tbl, inq_pay, inq_dst, inq_len, st, *arr, r, t)

    def exchange(snd_pay, snd_dst, snd_prt, snd_val, pending):
        row = jnp.concatenate([
            snd_dst.astype(jnp.float32)[:, None],
            snd_prt.astype(jnp.float32)[:, None],
            snd_val.astype(jnp.float32)[:, None],
            snd_pay,
        ], axis=1)                                           # (NL, F)
        if packed_ok:
            # one collective for the whole fabric: row li rides at the
            # destination's index
            buf = _pvary(jnp.zeros((n, F), jnp.float32), comm)
            buf = buf.at[nbr_r].set(row)
            got = lax.all_to_all(buf, comm.axis, 0, 0, tiled=True)
            rows = got[src_r]                                # (NL, F)
        else:
            rows = jnp.stack([
                lax.ppermute(row[li], comm.axis, pairs)
                for li, (_lid, pairs) in enumerate(links)
            ])
        live = lax.psum(pending, comm.axis)
        arr = (rows[:, 3:], rows[:, 0].astype(jnp.int32),
               rows[:, 1].astype(jnp.int32), rows[:, 2] > 0.5)
        return arr, live

    # batch size must divide n_steps: the drain check only runs between
    # batches, and a batch straddling the n_steps bound would tick a
    # still-live network past the cycle budget the scalar reference stops
    # at (idle ticks are identity, over-budget *live* ticks are not)
    B = max(1, min(int(cfg.tick_batch), int(n_steps)))
    while n_steps % B:
        B -= 1
    if obs.TRACING:
        obs.emit("router.tick_batch", batch=int(B),
                 n_batches=int(n_steps) // int(B))

    # early exit without while_loop: a scan over n_steps // B batches
    # whose body is a cond — once the pending count reports the network
    # drained, the remaining batches take the identity branch (the taken
    # branch is all XLA executes, so drained batches cost ~nothing).
    # cond + scan both carry transpose rules, which keeps the packet
    # datapath reverse-differentiable end to end (the training path
    # differentiates straight through the router, like the scalar
    # reference's concrete-bound fori_loop); while_loop does not.
    def batch(carry):
        st, arr, t, live = carry
        for _ in range(B):
            st, sp, sd, sq, sv, pend = tick(st, arr, t)
            arr, live = exchange(sp, sd, sq, sv, pend)
            t = t + 1
        return st, arr, t, live

    def body(carry, _):
        return lax.cond(carry[3] > 0, batch, lambda c: c, carry), None

    st0, arr0 = init()
    (st, arr, t, _live), _ = lax.scan(
        body,
        (st0, arr0, jnp.asarray(0, jnp.int32), live0),
        None, length=n_steps // B,
    )
    # the final exchange's arrivals are still in flight at loop exit
    st = router_absorb(spec, st, *arr, r, t - 1)
    return st["out_pay"], st["out_cnt"], st["overflow"], st["t_done"]
