"""Continuous-batching serve loop on persistent SMI channels.

The wave engine (serving/engine.py) admits requests only at wave
boundaries because the batch shares one cache position — correct, but a
request arriving mid-wave waits for the whole wave to drain.  This module
is the production loop:

* **per-slot positions** — ``pos`` is a (B,) vector (decode_attention
  generalises bit-identically from the scalar wave case), so every slot
  advances independently;
* **per-slot admission/invalidation** — a request lands in *any* free
  slot; :func:`serve_reset_slot` invalidates exactly that slot's rows
  across every cache leaf (``slot_pos`` rows back to -1, state to 0)
  without touching its batch-mates, so nothing ever leaks between
  requests;
* **prefill/decode overlap** — a tick carries at most one prompt chunk
  of up to :data:`PREFILL_CHUNK` tokens for one slot, in the same step
  and the same matmuls as its batch-mates' decode rows
  (:func:`~repro.models.lm_mixed_step`), so there is no prefill barrier.
  Slots whose prompts are not yet in the cache take chunks in admission
  order, and the first token comes from the last chunk.  Where the
  config has recurrent, windowed, MoE or codebook layers
  (:func:`~repro.models.chunked_prefill_ok`), or a prompt does not fit
  in the capacity, the slot replays its prompt one token a tick through
  its decode row instead (the per-slot cursor);
* **persistent channels** — under tensor parallelism the decode step's
  layer channels come from a :class:`~repro.channels.ChannelPool`
  threaded through ``ParallelCtx(channels=pool)``: one
  ``ChannelSpec(persistent=True)`` per layer tag, claimed once, reused
  every step, released only at :meth:`ContinuousEngine.shutdown`;
* **streaming migration** — a slot's cache rows (an opaque byte image
  across every leaf) stream to the root over a persistent gather channel
  and back out over a scatter channel, both tallying under
  ``"serve.migrate"``, with the apps-layer start/finish split
  (apps/halo.py): decode ticks for the other slots run between the two
  legs while the migrating slot's image is in flight.

Migration always rides the lossless static schedule on a raw wire: the
image is reinterpreted bytes (bf16 KV, int32 positions, f32 recurrent
state) and a lossy or reordering wire would corrupt it.

Every tick runs inside a ``serve.tick`` span with one child span a phase
(``serve.admit``, ``serve.prepare``, ``serve.dispatch``, ``serve.sample``,
``serve.harvest``; ``repro.obs.trace.span``), and the programs it
dispatches carry stable names (``serve_decode_step``,
``serve_mixed_step``, ``serve_reset_slot``, ``serve_greedy``).  The
engine's :class:`~repro.serving.record.EngineRecord` times each request
and each phase on the host, always.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..mesh.api import ParallelCtx
from ..models import (
    chunked_prefill_ok,
    lm_caches,
    lm_decode_step,
    lm_mixed_step,
)
from ..obs import metrics as obs_metrics
from ..obs.trace import span
from ..parallel import ledger
from .engine import Request
from .record import EngineRecord

#: the stats tag migration traffic tallies under (pool-prefixed ->
#: "serve.migrate"); gather and scatter legs share it
MIGRATE_TAG = "migrate"

#: prompt tokens a tick takes into the cache for one slot, riding in the
#: decode step (Sarathi-Serve's stall-free batching, arXiv:2403.02310).
#: The decode step already reads every weight once a tick (yi-6b's 0.82 GB
#: of bf16, ~1.0 ms at a v5e's 819 GB/s); 32 decode rows plus 128 chunk
#: rows are 160 rows of matmul, ~130 GFLOP, ~0.7 ms at its 197 TFLOP/s,
#: so the chunk's rows reuse that read instead of adding a tick each.  The
#: chunk's f32 scores over one 2048-row slot are 33.6 MB a layer.  On a
#: v5e the step at 32 x 2048 rows took no more device time with a chunk
#: of 128 than with one of 64, and no more than without one (PERF.md §6).
#: One fixed width (``min(PREFILL_CHUNK, capacity)``), so the mixed step
#: compiles once.
PREFILL_CHUNK = 128

#: sentinel occupying a slot whose cache image is in flight (migration):
#: not decodable, not admittable
_MIGRATING = object()


# ------------------------------------------------------------- cache rows
#
# Cache trees are {"periods": tuple-of-stacked-block-trees, "rem":
# tuple-of-block-trees} (models/transformer.py): leaves under "periods"
# carry a leading layer dim, so their batch dim is 1; everything else is
# batch-dim 0.  ``slot_pos`` leaves hold -1 for "no entry".


def _batch_dim(path) -> int:
    return 1 if any(getattr(k, "key", None) == "periods" for k in path) else 0


def _is_slot_pos(path) -> bool:
    return any(getattr(k, "key", None) == "slot_pos" for k in path)


def serve_reset_slot(caches, slot):
    """Invalidate one batch slot across every cache leaf: its ``slot_pos``
    rows go to -1 (no valid entry) and all other state to 0.  The other
    slots' rows are untouched — this is the per-slot cache invalidation
    continuous admission relies on."""
    def one(path, leaf):
        bdim = _batch_dim(path)
        fill = -1 if _is_slot_pos(path) else 0
        row = jnp.full(
            leaf.shape[:bdim] + (1,) + leaf.shape[bdim + 1:], fill, leaf.dtype
        )
        return lax.dynamic_update_slice_in_dim(leaf, row, slot, bdim)

    return jax.tree_util.tree_map_with_path(one, caches)


@jax.jit
def serve_greedy(logits, chunk_logits=None):
    """The greedy pick of every slot: the best vocabulary entry (axis 1);
    with a prompt chunk's ``chunk_logits`` (V,), its pick follows as row
    B."""
    if chunk_logits is not None:
        logits = jnp.concatenate([logits, chunk_logits[None]])
    return jnp.argmax(logits, axis=1)


class _phase(span):
    """One phase of a tick: its span, then its end stamped onto ``bounds``,
    the tick's host times (``time.time_ns()``) that the record keeps."""

    __slots__ = ("bounds",)

    def __init__(self, name: str, bounds: list):
        super().__init__(name)
        self.bounds = bounds

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self.bounds.append(time.time_ns())
        return False


def copy_slot(caches, src, dst):
    """Local slot-to-slot row copy — the exactness oracle for the
    streamed migration path."""
    def one(path, leaf):
        bdim = _batch_dim(path)
        row = lax.dynamic_slice_in_dim(leaf, src, 1, bdim)
        return lax.dynamic_update_slice_in_dim(leaf, row, dst, bdim)

    return jax.tree_util.tree_map_with_path(one, caches)


def pack_slot(caches, slot):
    """One slot's rows across every (local) cache leaf as a flat (N,)
    uint8 image, leaves in tree-flatten order.  Reinterpreted bytes
    (bitcast), so the image is exact for every leaf dtype."""
    bufs = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        row = lax.dynamic_slice_in_dim(leaf, slot, 1, _batch_dim(path))
        flat = row.reshape(-1)
        if flat.dtype != jnp.uint8:
            flat = lax.bitcast_convert_type(flat, jnp.uint8)
        bufs.append(flat.reshape(-1))
    return jnp.concatenate(bufs)


def unpack_slot(caches, image, slot):
    """Inverse of :func:`pack_slot`: write the (N,) uint8 image back into
    ``slot``'s rows across every cache leaf."""
    leaves = jax.tree_util.tree_leaves_with_path(caches)
    out, off = [], 0
    for path, leaf in leaves:
        bdim = _batch_dim(path)
        row_shape = leaf.shape[:bdim] + (1,) + leaf.shape[bdim + 1:]
        n = int(np.prod(row_shape))
        nbytes = n * leaf.dtype.itemsize
        piece = lax.slice_in_dim(image, off, off + nbytes, axis=0)
        off += nbytes
        if leaf.dtype != jnp.uint8:
            piece = lax.bitcast_convert_type(
                piece.reshape(n, leaf.dtype.itemsize), leaf.dtype
            )
        out.append(lax.dynamic_update_slice_in_dim(
            leaf, piece.reshape(row_shape), slot, bdim
        ))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(caches), out
    )


def slot_nbytes(cache_shapes) -> int:
    """Bytes of one slot's packed image (for the migration channel's
    predicted cost)."""
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache_shapes):
        bdim = _batch_dim(path)
        shape = leaf.shape[:bdim] + (1,) + leaf.shape[bdim + 1:]
        total += int(np.prod(shape)) * np.dtype(leaf.dtype).itemsize
    return total


# --------------------------------------------------------- migration legs


def open_migration(pool):
    """The persistent gather/scatter channel pair one engine's migrations
    ride — both tagged ``serve.migrate``, both pinned to the lossless
    static schedule on a raw wire (the image is reinterpreted bytes)."""
    g = pool.spec(MIGRATE_TAG, kind="gather", transport="static",
                  wire="raw", key=pool.retag(MIGRATE_TAG) + "#gather")
    s = pool.spec(MIGRATE_TAG, kind="scatter", transport="static",
                  wire="raw", key=pool.retag(MIGRATE_TAG) + "#scatter")
    return g, s


def migrate_gather(caches, slot, gspec):
    """Start leg: pack ``slot``'s local rows and stream every rank's image
    to the root over the persistent gather channel.  Returns the in-flight
    (P, N) buffer (meaningful at the root)."""
    from ..channels.channel import _tagged
    from ..core.collectives import _stream_gather_impl

    image = pack_slot(caches, slot)
    t = ledger.attach(gspec.resolve())
    with _tagged(t, gspec.stats_tag):
        return _stream_gather_impl(image[None], gspec.comm, root=gspec.root,
                                   transport=t)


def migrate_scatter(caches, inflight, slot, sspec):
    """Finish leg: stream each rank's image back out of the root over the
    persistent scatter channel and write it into ``slot``'s rows."""
    from ..channels.channel import _tagged
    from ..core.collectives import _stream_scatter_impl

    t = ledger.attach(sspec.resolve())
    with _tagged(t, sspec.stats_tag):
        image = _stream_scatter_impl(inflight, sspec.comm, root=sspec.root,
                                     transport=t)
    return unpack_slot(caches, image[0], slot)


# ------------------------------------------------------------- the engine


class ContinuousEngine:
    """Continuous-batching serve loop; greedy sampling, deterministic.

    Single-device by default (``ctx=None``); pass the ``runtime`` dict
    from :func:`repro.launch.steps.build_continuous_serve` to run the
    tensor-parallel decode step on persistent channels.

    A request's greedy output is bit-identical to the wave engine's for
    the same params: each slot's computation depends only on its own row
    (per-slot positions, per-row cache masking), so batch-mates — and
    when they were admitted — cannot perturb it.

    The engine's :attr:`record` is published in
    ``repro.obs.metrics.REGISTRY`` under ``name``, replacing the record of
    an earlier engine of that name.
    """

    def __init__(self, cfg, params, *, ctx: ParallelCtx | None = None,
                 batch_slots: int = 4, capacity: int = 128,
                 eos: int | None = None, runtime: dict | None = None,
                 name: str = "serve"):
        self.cfg = cfg
        self.params = params
        self.eos = eos
        if runtime is not None:
            self.ctx = runtime["ctx"]
            self.pool = runtime.get("pool")
            self.B = runtime["batch_slots"]
            self.capacity = runtime["capacity"]
            self.caches = runtime["init_caches"]()
            self._step = runtime["step"]
            self._mixed = runtime["mixed_step"]
            self._reset = runtime["reset"]
            self._mig_start = runtime["migrate_start"]
            self._mig_finish = runtime["migrate_finish"]
        else:
            self.ctx = ctx or ParallelCtx()
            self.pool = None
            self.B = batch_slots
            self.capacity = capacity
            self.caches = lm_caches(cfg, batch_slots, capacity=capacity,
                                    ctx=self.ctx)
            ctx = self.ctx

            def serve_decode_step(p, c, t, pos):
                return lm_decode_step(p, c, t, pos, cfg, ctx)

            def serve_mixed_step(p, c, t, pos, ct, cs, c0, cn):
                return lm_mixed_step(p, c, t, pos, ct, cs, c0, cn, cfg, ctx)

            self._step = jax.jit(serve_decode_step)
            self._mixed = jax.jit(serve_mixed_step)
            self._reset = jax.jit(serve_reset_slot, donate_argnums=(0,))
            # single-device "migration": the packed image round-trips
            # locally (the comm legs need a TP runtime)
            self._mig_start = jax.jit(pack_slot)
            self._mig_finish = jax.jit(unpack_slot, donate_argnums=(0,))
        B = self.B
        self.slot_req: list = [None] * B
        self.queue: list[Request] = []
        self.pos = np.zeros(B, dtype=np.int32)      # per-slot next position
        self.cursor = np.zeros(B, dtype=np.int64)   # per-slot prompt cursor
        tok_shape = (B, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B,)
        self._cur = np.zeros(tok_shape, dtype=np.int32)
        # prompt chunk width; 0 where every prompt is replayed
        self.chunk = (min(PREFILL_CHUNK, self.capacity)
                      if chunked_prefill_ok(cfg) else 0)
        # admitted requests whose prompts are not yet all in the cache,
        # taken in chunks, in admission order
        self._filling: list[Request] = []
        self._decoding = np.zeros(B, dtype=bool)   # slots decoding a tick
        self.steps_done = 0
        self.admit_step: dict[int, int] = {}   # uid -> tick admitted
        self.finish_step: dict[int, int] = {}  # uid -> tick completed
        # host-time record of requests and ticks, found by name in the
        # metrics registry (a new engine of the same name replaces it)
        self.record = EngineRecord()
        self._runtime = obs_metrics.runtime_counters()
        obs_metrics.REGISTRY.publish(name, self.record)

    # -- queue / admission ---------------------------------------------------

    def submit(self, req: Request):
        self.record.stamp(req.uid, "submit", time.time_ns())
        self.queue.append(req)

    @staticmethod
    def _active(r) -> bool:
        return r is not None and r is not _MIGRATING

    def _admit(self) -> int:
        """Admit waiting requests into free slots — any free slot, any
        time; only that slot's cache rows are invalidated."""
        n = 0
        for i in range(self.B):
            if self.slot_req[i] is None and self.queue:
                req = self.queue.pop(0)
                with span("serve.reset", uid=req.uid, slot=i):
                    self.caches = self._reset(self.caches, np.int32(i))
                self.record.stamp(req.uid, "admit", time.time_ns())
                self.slot_req[i] = req
                self.pos[i] = 0
                self.cursor[i] = 0
                self._cur[i] = 0
                if self.chunk and 0 < len(req.prompt) <= self.capacity:
                    self._filling.append(req)
                self.admit_step[req.uid] = self.steps_done
                n += 1
        return n

    # -- the decode tick -----------------------------------------------------

    def tick(self) -> list[Request]:
        """Admit, run ONE step for every occupied slot (a prompt chunk, or
        prompt replay, and generation overlap in the same step), harvest
        completions.  Returns the requests completed this tick.

        Each phase runs in its own span and is timed into the record;
        ``serve.sample`` is where the host waits for the chip."""
        rt = self._runtime
        compiles, gc_ns = rt.compiles, rt.gc_ns
        t = [time.time_ns()]
        with span("serve.tick", step=self.steps_done):
            with _phase("serve.admit", t):
                self._admit()
            if not any(self._active(r) for r in self.slot_req):
                return []
            with _phase("serve.prepare", t):
                chunk, replayed = self._prepare()
            with _phase("serve.dispatch", t):
                tok = jnp.asarray(self._cur)
                if chunk is None:
                    logits, self.caches = self._step(
                        self.params, self.caches, tok, jnp.asarray(self.pos))
                    chunk_logits = None
                else:
                    _, slot, start, n, chunk_tok = chunk
                    pos = jnp.asarray(np.where(self._decoding, self.pos, -1))
                    logits, chunk_logits, self.caches = self._mixed(
                        self.params, self.caches, tok, pos,
                        jnp.asarray(chunk_tok), np.int32(slot),
                        np.int32(start), np.int32(n))
            with _phase("serve.sample", t):
                nxt = np.asarray(serve_greedy(logits, chunk_logits))
            with _phase("serve.harvest", t):
                done = self._harvest(nxt, t[-1], chunk)
        self.record.tick(t, rt.compiles - compiles, rt.gc_ns - gc_ns,
                         0 if chunk is None else chunk[3], replayed)
        return done

    def _prepare(self):
        """Each decoding slot's input token, and the tick's prompt chunk:
        the next ``min(chunk, left)`` prompt tokens of the first filling
        request in a slot, as ``(req, slot, start, n, tokens)`` (tokens
        padded to the chunk width), or None.  Returns the chunk and the
        number of prompt tokens replayed through decode rows."""
        slot_of = {id(r): i for i, r in enumerate(self.slot_req)
                   if self._active(r)}
        filling = {id(r) for r in self._filling}
        replayed = 0
        for i, req in enumerate(self.slot_req):
            self._decoding[i] = self._active(req) and id(req) not in filling
            if not self._decoding[i]:
                self._cur[i] = 0
            elif self.cursor[i] < len(req.prompt):
                self._cur[i] = req.prompt[int(self.cursor[i])]
                replayed += 1
            # else: keep the sampled token from the last tick
        req = next((r for r in self._filling if id(r) in slot_of), None)
        if req is None:
            return None, replayed
        slot = slot_of[id(req)]
        start = int(self.cursor[slot])
        part = req.prompt[start:start + self.chunk]
        tokens = np.zeros(self.chunk, dtype=np.int32)
        tokens[:len(part)] = part
        return (req, slot, start, len(part), tokens), replayed

    def _harvest(self, nxt, now: int, chunk) -> list[Request]:
        """Advance every decoding slot by the step's token and the chunk's
        slot by its tokens, append generated tokens (stamped ``now``, when
        they reached the host), free finished slots."""
        done: list[Request] = []
        for i, req in enumerate(self.slot_req):
            if not self._decoding[i]:
                continue
            self.pos[i] += 1
            self.cursor[i] += 1
            if self.cursor[i] >= len(req.prompt):
                self._emit(i, req, nxt[i], now, done)
        if chunk is not None:
            req, slot, _, n, _ = chunk
            self.pos[slot] += n
            self.cursor[slot] += n
            if self.cursor[slot] >= len(req.prompt):
                self._filling = [r for r in self._filling if r is not req]
                self._emit(slot, req, nxt[self.B], now, done)
        self.steps_done += 1
        return done

    def _emit(self, i: int, req: Request, tok, now: int, done: list):
        """Slot ``i``'s request takes generated token ``tok``; a finished
        request goes to ``done`` and its slot is freed."""
        req.out.append(tok.tolist() if tok.ndim else int(tok))
        if len(req.out) == 1:
            self.record.stamp(req.uid, "first", now)
        self._cur[i] = tok
        hit_eos = (self.eos is not None and np.ndim(tok) == 0
                   and int(tok) == self.eos)
        if len(req.out) >= req.max_new or hit_eos:
            req.done = True
            self.finish_step[req.uid] = self.steps_done + 1
            self.record.stamp(req.uid, "finish", now)
            done.append(req)
            self.slot_req[i] = None   # freed NOW: no wave barrier

    def run(self, *, max_steps: int = 256, arrivals=None) -> list[Request]:
        """Drain the queue; returns completed requests.

        ``arrivals`` is an optional ``[(tick, Request), ...]`` schedule
        keyed on the engine's global tick clock (``steps_done``), so
        latency benchmarks can replay a Poisson trace against continuous
        admission."""
        completed: list[Request] = []
        pending = sorted(arrivals, key=lambda a: a[0]) if arrivals else []
        steps = 0
        while (pending or any(r is not None for r in self.slot_req)
               or self.queue) and steps < max_steps:
            while pending and pending[0][0] <= self.steps_done:
                self.queue.append(pending.pop(0)[1])
            if not self.queue and \
                    not any(self._active(r) for r in self.slot_req):
                self.steps_done += 1  # idle tick: waiting on arrivals
                steps += 1
                continue
            completed.extend(self.tick())
            steps += 1
        return completed

    # -- migration -----------------------------------------------------------

    def migrate(self, src: int, dst: int, *, overlap_ticks: int = 0):
        """Move the request in slot ``src`` into free slot ``dst`` by
        streaming its cache image over the migration channels
        (start/finish split): ``overlap_ticks`` decode ticks for the
        other slots run between the gather and scatter legs while the
        image is in flight.  Both slots are held out of decoding (and
        admission) for the duration."""
        req = self.slot_req[src]
        assert self._active(req), "source slot must hold a request"
        assert self.slot_req[dst] is None, "destination slot must be free"
        with span("serve.migrate.start", uid=req.uid, slot=src):
            inflight = self._mig_start(self.caches, np.int32(src))
        self.slot_req[src] = _MIGRATING
        self.slot_req[dst] = _MIGRATING
        state = (self.pos[src], self.cursor[src], self._cur[src].copy())
        for _ in range(overlap_ticks):
            self.tick()
        with span("serve.migrate.finish", uid=req.uid, slot=dst):
            self.caches = self._mig_finish(self.caches, inflight,
                                           np.int32(dst))
        self.slot_req[src] = None
        self.slot_req[dst] = req
        self.pos[dst], self.cursor[dst], self._cur[dst] = state
        return req

    # -- lifecycle -----------------------------------------------------------

    def shutdown(self):
        """Release the pool's persistent port claims (the ONLY point a
        persistent channel's port returns to the allocator)."""
        if self.pool is not None and not self.pool.closed:
            self.pool.close()

    def __enter__(self) -> "ContinuousEngine":
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
