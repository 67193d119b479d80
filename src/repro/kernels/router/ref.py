"""Pure single-tick datapath of the store-and-forward router.

One tick of ``core/router.py`` as whole-state array ops — no per-link
Python loop, no per-arrival scalar scan.  The lax "vector" implementation
executes exactly this function, and the Pallas kernel
(``kernel.py``) restates it in ops Mosaic lowers; the seed's per-link
scalar loop is kept in ``core/router.py`` as the reference the
equivalence tests diff against.

Why one-shot arbitration is exact: the routing table maps each candidate
source (its head packet's destination) to exactly *one* link id, so the
per-link availability sets are disjoint across links — the sequential
``taken`` mask of the scalar reference can never exclude a candidate a
later link would otherwise have selected.  Arbitrating every link with one
masked argmax over the (NL, S) availability matrix is therefore
tick-for-tick identical to the scalar loop, R-stickiness, switch-bubble
and all.

Sequential-absorb equivalence: the scalar reference delivers/parks
arrivals one link at a time, each seeing the counters the previous arrival
updated.  The vectorized form reproduces that with exclusive prefix sums
in link order: arrival ``li``'s delivery slot is ``out_cnt[port] + (number
of earlier arrivals this tick delivering to the same port)``, and its
transit-tail offset is the count of earlier parked arrivals — the same
slots, computed in one shot and written with masked scatters
(out-of-bounds index + ``mode="drop"`` realises the capacity drop).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class TickSpec:
    """Static shape/config of one router tick (hashable, trace-stable)."""

    n: int                    # ranks
    n_ports: int
    fifo_cap: int
    transit_cap: int
    out_cap: int
    pkt_elems: int
    R: int
    switch_bubble: bool
    link_ids: tuple[int, ...]  # physical id of each link, in link order

    @property
    def n_links(self) -> int:
        return len(self.link_ids)

    @property
    def n_srcs(self) -> int:
        """Arbitration candidates per link: the input FIFOs + transit."""
        return self.n_ports + 1


def tick_spec_of(cfg, n: int, link_ids) -> TickSpec:
    """Build a TickSpec from a ``core.router.RouterConfig``."""
    return TickSpec(
        n=n, n_ports=cfg.n_ports, fifo_cap=cfg.fifo_cap,
        transit_cap=cfg.transit_cap, out_cap=cfg.out_cap,
        pkt_elems=cfg.pkt_elems, R=cfg.R,
        switch_bubble=cfg.switch_bubble, link_ids=tuple(link_ids),
    )


def _i32(x):
    return x.astype(jnp.int32)


def router_absorb(spec: TickSpec, st, arr_pay, arr_dst, arr_prt, arr_val,
                  r, t):
    """Absorb one tick's arrivals: deliver (dst == me) or park in transit.

    ``arr_*`` are the NL link arrivals in link order; ``t`` labels the tick
    the arrivals completed (the ``t_done`` stamp).  A delivery past
    ``out_cap`` and a park past ``transit_cap`` both drop the packet and
    count it in ``overflow``.
    """
    NP, NL = spec.n_ports, spec.n_links
    if NL == 0:
        return st
    mine = jnp.logical_and(arr_val, arr_dst == r)            # (NL,)
    fwd = jnp.logical_and(arr_val, arr_dst != r)
    prt = jnp.clip(arr_prt, 0, NP - 1)

    # -- deliveries: per-port slots via exclusive prefix sums in link order
    hot = jnp.logical_and(mine[:, None],
                          prt[:, None] == jnp.arange(NP)[None, :])  # (NL,NP)
    hot_i = _i32(hot)
    prior = jnp.cumsum(hot_i, axis=0) - hot_i
    slot = st["out_cnt"][prt] + \
        jnp.take_along_axis(prior, prt[:, None], axis=1)[:, 0]
    ok_del = jnp.logical_and(mine, slot < spec.out_cap)
    row = jnp.where(ok_del, prt, NP)              # OOB row/col => dropped
    col = jnp.where(ok_del, slot, spec.out_cap)
    st["out_pay"] = st["out_pay"].at[row, col].set(arr_pay, mode="drop")
    st["out_cnt"] = st["out_cnt"] + \
        jnp.sum(_i32(jnp.logical_and(hot, ok_del[:, None])), axis=0)
    st["overflow"] = st["overflow"] + \
        jnp.sum(_i32(jnp.logical_and(mine, ~ok_del)))
    st["t_done"] = jnp.where(ok_del.any(), _i32(t), st["t_done"])

    # -- transit parking: ring-buffer tails via exclusive prefix sum
    fwd_i = _i32(fwd)
    off = jnp.cumsum(fwd_i) - fwd_i                          # (NL,)
    room = (st["tr_cnt"] + off) < spec.transit_cap
    ok_park = jnp.logical_and(fwd, room)
    tail = (st["tr_head"] + st["tr_cnt"] + off) % spec.transit_cap
    idx = jnp.where(ok_park, tail, spec.transit_cap)
    st["tr_pay"] = st["tr_pay"].at[idx].set(arr_pay, mode="drop")
    st["tr_dst"] = st["tr_dst"].at[idx].set(arr_dst, mode="drop")
    st["tr_port"] = st["tr_port"].at[idx].set(arr_prt, mode="drop")
    st["tr_cnt"] = st["tr_cnt"] + jnp.sum(_i32(ok_park))
    st["overflow"] = st["overflow"] + \
        jnp.sum(_i32(jnp.logical_and(fwd, ~room)))
    return st


def router_arbitrate(spec: TickSpec, my_tbl, inq_pay, inq_dst, inq_len,
                     st, r):
    """Arbitrate all links in one shot and pop the selected sources.

    Returns ``(st, snd_pay, snd_dst, snd_prt, snd_val, pending)`` —
    the NL outgoing link rows plus the rank's remaining-work count
    (staged + parked + in flight) for the early-exit ticker.
    """
    NP, S = spec.n_ports, spec.n_srcs
    n = spec.n
    link_ids = jnp.asarray(spec.link_ids, jnp.int32)

    # candidate heads: sources 0..NP-1 = input FIFOs, S-1 = transit
    hclip = jnp.minimum(st["inq_head"], spec.fifo_cap - 1)
    fifo_pay = jnp.take_along_axis(
        inq_pay, hclip[:, None, None], axis=1)[:, 0]         # (NP, E)
    fifo_dst = jnp.take_along_axis(inq_dst, hclip[:, None], axis=1)[:, 0]
    fifo_has = st["inq_head"] < inq_len
    th = st["tr_head"] % spec.transit_cap
    cand_pay = jnp.concatenate([fifo_pay, st["tr_pay"][th][None]], axis=0)
    cand_dst = jnp.concatenate([fifo_dst, st["tr_dst"][th][None]])
    cand_prt = jnp.concatenate(
        [jnp.arange(NP, dtype=jnp.int32), st["tr_port"][th][None]])
    cand_has = jnp.concatenate([fifo_has, (st["tr_cnt"] > 0)[None]])

    want = jnp.where(cand_dst == r, -2,
                     my_tbl[jnp.clip(cand_dst, 0, n - 1)])   # (S,)
    A = jnp.logical_and(cand_has[None, :],
                        want[None, :] == link_ids[:, None])  # (NL, S)

    last = st["last_src"]
    tr_want = A[:, S - 1]
    keep = jnp.logical_and(
        st["stick"] < spec.R,
        jnp.take_along_axis(
            A, jnp.clip(last, 0, S - 1)[:, None], axis=1)[:, 0],
    )
    idxs = (last[:, None] + 1 + jnp.arange(S)[None, :]) % S  # (NL, S)
    rot = jnp.take_along_axis(A, idxs, axis=1)
    off = jnp.argmax(rot, axis=1)
    rr = jnp.take_along_axis(idxs, off[:, None], axis=1)[:, 0]
    chosen = jnp.where(tr_want, S - 1, jnp.where(keep, last, rr))
    any_avail = A.any(axis=1)
    if spec.switch_bubble:
        switching = jnp.logical_and(any_avail, chosen != last)
        send = jnp.logical_and(any_avail, ~switching)
    else:
        send = any_avail
    st["last_src"] = jnp.where(any_avail, chosen, last)
    st["stick"] = jnp.where(
        jnp.logical_and(send, chosen == last), st["stick"] + 1, 0)
    sel = jnp.where(send, chosen, -1)                        # (NL,)

    # pops (availability sets are disjoint: each source selected at most once)
    pop_fifo = jnp.sum(
        _i32(sel[:, None] == jnp.arange(NP)[None, :]), axis=0)
    st["inq_head"] = st["inq_head"] + pop_fifo
    tr_pops = jnp.sum(_i32(sel == S - 1))
    st["tr_head"] = st["tr_head"] + tr_pops
    st["tr_cnt"] = st["tr_cnt"] - tr_pops

    # outgoing rows (invalid selections ride as bubbles)
    cs = jnp.clip(sel, 0, S - 1)
    snd_val = sel >= 0
    snd_pay = cand_pay[cs]                                   # (NL, E)
    snd_dst = jnp.where(snd_val, cand_dst[cs], -1)
    snd_prt = jnp.where(snd_val, cand_prt[cs], 0)

    pending = jnp.sum(inq_len - st["inq_head"]) + st["tr_cnt"] + \
        jnp.sum(_i32(snd_val))
    return st, snd_pay, snd_dst, snd_prt, snd_val, _i32(pending)


def router_tick(spec: TickSpec, my_tbl, inq_pay, inq_dst, inq_len, st,
                arr_pay, arr_dst, arr_prt, arr_val, r, t):
    """One full tick: absorb the previous tick's arrivals (labelled
    ``t - 1``), then arbitrate/pop the outgoing rows for tick ``t``."""
    st = router_absorb(spec, st, arr_pay, arr_dst, arr_prt, arr_val,
                       r, t - 1)
    return router_arbitrate(spec, my_tbl, inq_pay, inq_dst, inq_len, st, r)
