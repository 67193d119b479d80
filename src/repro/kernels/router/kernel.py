"""Pallas tick kernel for the packet router (DESIGN.md §10).

One ``pallas_call`` executes a full router tick — absorb the previous
tick's arrivals, arbitrate all links, pop the selected FIFO heads — with
every piece of mutable router state (input-FIFO heads, transit ring
buffer, delivery buffers, arbiter latch/stickiness, counters) passed in
and aliased onto the corresponding output via ``input_output_aliases``.

The tick semantics are ``ref.router_tick``'s, restated in the ops Mosaic
lowers: the tick is tiny (a few ports, links and FIFO slots), so every
index becomes a static loop or a comparison against an iota, every gather
a chain of selects and every scatter a masked select — no ``cumsum``, no
``argmax``, no dynamic gather or scatter.  Arrivals are absorbed one link
at a time in link order, as the scalar reference does; the prefix sums of
``ref.router_absorb`` compute the same slots in one shot.  All values are
2-D: scalars ride as (1, 1) tiles and 1-D state as (1, k) rows, and the
wrapper reshapes at the boundary so callers keep the reference shapes.
Selects move payloads bit for bit, so the kernel equals the vector
datapath exactly — what the equivalence tests in ``tests/test_router.py``
assert.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..common import pallas_call
from .ref import TickSpec

#: state-dict keys in the fixed ref-argument order of the kernel
STATE_KEYS = (
    "inq_head", "tr_pay", "tr_dst", "tr_port", "tr_head", "tr_cnt",
    "out_pay", "out_cnt", "overflow", "last_src", "stick", "t_done",
)

#: keys whose carried shape is 0-D / 1-D and rides as (1, k) in the kernel
_FLAT = {"inq_head", "tr_dst", "tr_port", "tr_head", "tr_cnt", "out_cnt",
         "overflow", "last_src", "stick", "t_done"}

i32 = jnp.int32


def _widen(k, v):
    if k == "out_pay":
        return v.reshape(-1, v.shape[-1])
    return v.reshape(1, -1) if k in _FLAT else v


def _narrow(k, v, like):
    return v.reshape(like.shape) if k in _FLAT or k == "out_pay" else v


def _lanes(k):
    return lax.broadcasted_iota(i32, (1, k), 1)


def _at(row, idx):
    """``row[idx]`` for a (1, k) int row and a (1, 1) index in range."""
    return jnp.sum(jnp.where(_lanes(row.shape[1]) == idx, row, 0), axis=1,
                   keepdims=True)


def _row_at(rows, idx):
    """Row ``idx`` of ``rows`` (k, E), (1, 1) index in range, bit-exact."""
    out = rows[0:1]
    for j in range(1, rows.shape[0]):
        out = jnp.where(idx == j, rows[j:j + 1], out)
    return out


def _put_row(rows, idx, row, ok):
    """``rows`` with row ``idx`` replaced by ``row`` where ``ok``.  The
    mask is a column first: Mosaic broadcasts a (1, 1) value along one
    axis at a time."""
    hit = lax.broadcasted_iota(i32, (rows.shape[0], 1), 0) == idx
    return jnp.where(jnp.logical_and(ok, hit), row, rows)


def _put(row, idx, v, ok):
    """(1, k) ``row`` with lane ``idx`` set to ``v`` where ``ok``."""
    return jnp.where(jnp.logical_and(ok, _lanes(row.shape[1]) == idx), v, row)


def _absorb(spec: TickSpec, st, arr_pay, arr_meta, r, t):
    NP, OC, TC = spec.n_ports, spec.out_cap, spec.transit_cap
    for li in range(spec.n_links):
        dst = arr_meta[0:1, li:li + 1]
        raw_prt = arr_meta[1:2, li:li + 1]
        val = arr_meta[2:3, li:li + 1] > 0
        pay = arr_pay[li:li + 1]
        mine = jnp.logical_and(val, dst == r)
        fwd = jnp.logical_and(val, dst != r)
        prt = jnp.clip(raw_prt, 0, NP - 1)

        cnt = _at(st["out_cnt"], prt)
        ok_del = jnp.logical_and(mine, cnt < OC)
        st["out_pay"] = _put_row(st["out_pay"], prt * OC + cnt, pay, ok_del)
        st["out_cnt"] = _put(st["out_cnt"], prt, cnt + 1, ok_del)
        st["overflow"] = st["overflow"] + \
            jnp.logical_and(mine, ~ok_del).astype(i32)
        st["t_done"] = jnp.where(ok_del, t, st["t_done"])

        room = st["tr_cnt"] < TC
        ok_park = jnp.logical_and(fwd, room)
        tail = (st["tr_head"] + st["tr_cnt"]) % TC
        st["tr_pay"] = _put_row(st["tr_pay"], tail, pay, ok_park)
        st["tr_dst"] = _put(st["tr_dst"], tail, dst, ok_park)
        st["tr_port"] = _put(st["tr_port"], tail, raw_prt, ok_park)
        st["tr_cnt"] = st["tr_cnt"] + ok_park.astype(i32)
        st["overflow"] = st["overflow"] + \
            jnp.logical_and(fwd, ~room).astype(i32)
    return st


def _arbitrate(spec: TickSpec, my_tbl, inq_pay, inq_dst, inq_len, st, r):
    NP, FC, TC, S, n = (spec.n_ports, spec.fifo_cap, spec.transit_cap,
                        spec.n_srcs, spec.n)
    NL, E = spec.n_links, spec.pkt_elems

    # candidate heads: sources 0..NP-1 = input FIFOs, S-1 = transit
    pay, dst, prt, has = [], [], [], []
    for p in range(NP):
        head = st["inq_head"][:, p:p + 1]
        h = jnp.minimum(head, FC - 1)
        pay.append(_row_at(inq_pay[p * FC:(p + 1) * FC], h))
        dst.append(_at(inq_dst[p:p + 1], h))
        prt.append(jnp.full((1, 1), p, i32))
        has.append(head < inq_len[:, p:p + 1])
    th = st["tr_head"] % TC
    pay.append(_row_at(st["tr_pay"], th))
    dst.append(_at(st["tr_dst"], th))
    prt.append(_at(st["tr_port"], th))
    has.append(st["tr_cnt"] > 0)
    want = [jnp.where(d == r, -2, _at(my_tbl, jnp.clip(d, 0, n - 1)))
            for d in dst]

    def pick(vals, idx):
        out = vals[0]
        for s in range(1, len(vals)):
            out = jnp.where(idx == s, vals[s], out)
        return out

    snd_pay = jnp.zeros((NL, E), inq_pay.dtype)
    snd_meta = jnp.zeros((3, NL), i32)
    pops = [jnp.zeros((1, 1), i32) for _ in range(S)]
    last_src, stick = st["last_src"], st["stick"]
    for li, lid in enumerate(spec.link_ids):
        # availability as 0/1 ints: Mosaic selects no booleans
        avail = [jnp.logical_and(h, w == lid).astype(i32)
                 for h, w in zip(has, want)]
        last = last_src[:, li:li + 1]
        keep = jnp.logical_and(stick[:, li:li + 1] < spec.R,
                               pick(avail, jnp.clip(last, 0, S - 1)) > 0)
        # round robin: the first available source after ``last``
        rr = (last + 1) % S
        for k in reversed(range(S)):
            idx = (last + 1 + k) % S
            rr = jnp.where(pick(avail, idx) > 0, idx, rr)
        chosen = jnp.where(avail[S - 1] > 0, S - 1,
                           jnp.where(keep, last, rr))
        any_avail = sum(avail[1:], avail[0]) > 0
        send = any_avail
        if spec.switch_bubble:
            send = jnp.logical_and(any_avail, chosen == last)
        lane = _lanes(NL) == li
        last_src = jnp.where(lane, jnp.where(any_avail, chosen, last),
                             last_src)
        stick = jnp.where(lane, jnp.where(
            jnp.logical_and(send, chosen == last),
            stick[:, li:li + 1] + 1, 0), stick)
        # availability sets are disjoint: each source is popped at most once
        for s in range(S):
            pops[s] = pops[s] + jnp.logical_and(send, chosen == s).astype(i32)
        cs = jnp.where(send, chosen, 0)
        row = lax.broadcasted_iota(i32, (NL, E), 0) == li
        snd_pay = jnp.where(row, pick(pay, cs), snd_pay)
        meta = jnp.concatenate([
            jnp.where(send, pick(dst, cs), -1),
            jnp.where(send, pick(prt, cs), 0),
            send.astype(i32)], axis=0)                      # (3, 1)
        snd_meta = jnp.where(lax.broadcasted_iota(i32, (3, NL), 1) == li,
                             meta, snd_meta)
    st["last_src"], st["stick"] = last_src, stick

    lanes = _lanes(NP)
    for p in range(NP):
        st["inq_head"] = st["inq_head"] + jnp.where(lanes == p, pops[p], 0)
    st["tr_head"] = st["tr_head"] + pops[S - 1]
    st["tr_cnt"] = st["tr_cnt"] - pops[S - 1]
    pending = (jnp.sum(inq_len - st["inq_head"], axis=1, keepdims=True)
               + st["tr_cnt"] + jnp.sum(snd_meta[2:3], axis=1, keepdims=True))
    return st, snd_pay, snd_meta, pending


def _make_kernel(spec: TickSpec):
    def kernel(my_tbl_ref, inq_pay_ref, inq_dst_ref, inq_len_ref, meta_ref,
               arr_pay_ref, arr_meta_ref, *state_refs):
        in_refs = state_refs[:len(STATE_KEYS)]
        out_refs = state_refs[len(STATE_KEYS):len(STATE_KEYS) * 2]
        snd_pay_ref, snd_meta_ref, pend_ref = state_refs[len(STATE_KEYS) * 2:]

        st = {k: ref[...] for k, ref in zip(STATE_KEYS, in_refs)}
        meta = meta_ref[...]
        r, t = meta[:, 0:1], meta[:, 1:2]
        st = _absorb(spec, st, arr_pay_ref[...], arr_meta_ref[...], r, t - 1)
        st, snd_pay, snd_meta, pending = _arbitrate(
            spec, my_tbl_ref[...], inq_pay_ref[...], inq_dst_ref[...],
            inq_len_ref[...], st, r)
        for k, ref in zip(STATE_KEYS, out_refs):
            ref[...] = st[k]
        snd_pay_ref[...] = snd_pay
        snd_meta_ref[...] = snd_meta
        pend_ref[...] = pending

    return kernel


@partial(jax.jit, static_argnames=("spec", "interpret"))
def router_tick_pallas(spec: TickSpec, my_tbl, inq_pay, inq_dst, inq_len,
                       st, arr_pay, arr_dst, arr_prt, arr_val, r, t, *,
                       interpret: bool = False):
    """``ref.router_tick`` as one Pallas kernel with in-place state.

    Same signature and returns as the reference; ``interpret=True`` runs
    the kernel through the Pallas interpreter (the CPU path).
    """
    NL, E = spec.n_links, spec.pkt_elems
    meta = jnp.stack([r, t]).astype(i32).reshape(1, 2)
    arr_meta = jnp.stack(
        [arr_dst.astype(i32), arr_prt.astype(i32), arr_val.astype(i32)])
    state_in = [_widen(k, st[k]) for k in STATE_KEYS]
    fixed = [my_tbl.reshape(1, -1), inq_pay.reshape(-1, E), inq_dst,
             inq_len.reshape(1, -1), meta, arr_pay, arr_meta]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in state_in]
    out_shape += [
        jax.ShapeDtypeStruct((NL, E), inq_pay.dtype),
        jax.ShapeDtypeStruct((3, NL), i32),
        jax.ShapeDtypeStruct((1, 1), i32),
    ]
    aliases = {len(fixed) + i: i for i in range(len(STATE_KEYS))}
    outs = pallas_call(
        _make_kernel(spec),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*fixed, *state_in)
    new_st = {
        k: _narrow(k, v, st[k])
        for k, v in zip(STATE_KEYS, outs[:len(STATE_KEYS)])
    }
    snd_pay, snd_meta, pending = outs[len(STATE_KEYS):]
    return (new_st, snd_pay, snd_meta[0], snd_meta[1], snd_meta[2] > 0,
            pending[0, 0])
