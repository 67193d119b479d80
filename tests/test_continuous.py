"""Continuous-batching engine tests (serving/continuous.py, DESIGN.md §13).

Four contracts:

* **wave-oracle bit-identity** — a request's greedy tokens are identical
  to the wave engine's (and to a solo run) no matter which slot it lands
  in, when it was admitted, or who its batch-mates are: per-slot
  positions, per-slot cache invalidation and per-slot prompt cursors must
  never leak state.  Checked single-device and tensor-parallel (ring and
  torus meshes, static and packet backends).
* **slot churn** — randomized staggered arrivals through a small slot
  pool drain completely and every output still equals its solo oracle
  (no cache-row leaks across admission/eviction churn).
* **migration exactness** — the packed byte image round-trip
  (``pack_slot`` -> ``unpack_slot``) equals the local ``copy_slot``
  oracle leaf-for-leaf, and a mid-decode slot migration never changes
  the request's remaining tokens.
* **persistent-channel lifecycle** — the serving pool's port claims
  survive trace exits and garbage collection, and are released only by
  engine shutdown / ``pool.close()``.

* **chunked prefill** — a dense-attention config takes each prompt that
  fits in the cache into it in chunks of up to ``PREFILL_CHUNK`` tokens,
  one chunk a tick in admission order, riding in the decode step; the
  greedy tokens still equal the wave oracle's, single-device and
  tensor-parallel, and a slot waiting for its chunks has no cache row
  written.  Other configs replay the prompt one token a tick.

Plus the serving twin of the train-step accounting regression:
``netsim.predict_decode_step_stats`` equals the traced channel ledger to
the byte per ``serve.*`` tag (the ``launch/serve --validate-comm``
contract).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke
from repro.mesh.api import ParallelCtx
from repro.models import init_lm, lm_caches
from repro.serving import ContinuousEngine, Request, ServeEngine
from repro.serving.continuous import (
    PREFILL_CHUNK,
    copy_slot,
    pack_slot,
    unpack_slot,
)

C = PREFILL_CHUNK
CAP = 4 * C   # cache rows in the chunked-prefill tests: room for 3C + 5


@pytest.fixture(scope="module")
def engine_setup():
    cfg = smoke(get_arch("yi-6b"))
    params = init_lm(jax.random.PRNGKey(0), cfg, ParallelCtx())
    return cfg, params


def _reqs(prompts, max_new=4):
    return [Request(uid=i, prompt=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]


def _solo_outs(cfg, params, prompts, *, max_new=4, engine_cls=ServeEngine):
    """{uid: tokens} with every request decoded alone — the oracle."""
    outs = {}
    for uid, p in enumerate(prompts):
        eng = engine_cls(cfg, params, batch_slots=1, capacity=64)
        eng.submit(Request(uid=uid, prompt=list(p), max_new=max_new))
        done = eng.run(max_steps=200)
        outs[uid] = done[0].out
    return outs


# ------------------------------------------------------ wave bit-identity


def test_continuous_matches_wave_engine(engine_setup):
    """Same prompts, same params: the continuous engine's greedy outputs
    are bit-identical to the wave engine's, slot-for-slot."""
    cfg, params = engine_setup
    prompts = [[5, 7, 9], [11, 3], [4], [8, 2, 6, 1]]

    wave = ServeEngine(cfg, params, batch_slots=2, capacity=64)
    for r in _reqs(prompts):
        wave.submit(r)
    wave_done = {r.uid: r.out for r in wave.run(max_steps=300)}

    cont = ContinuousEngine(cfg, params, batch_slots=2, capacity=64)
    for r in _reqs(prompts):
        cont.submit(r)
    cont_done = {r.uid: r.out for r in cont.run(max_steps=300)}

    assert sorted(cont_done) == sorted(wave_done) == [0, 1, 2, 3]
    for uid in wave_done:
        assert cont_done[uid] == wave_done[uid], f"uid {uid} diverged"


def test_mid_stream_admission_does_not_perturb_residents(engine_setup):
    """A request admitted into a freed slot mid-decode leaves its
    still-running batch-mates' outputs untouched — and its own output
    equals its solo run (the whole point of continuous batching)."""
    cfg, params = engine_setup
    prompts = [[5, 7, 9, 2], [11, 3], [6, 1, 4]]
    solo = _solo_outs(cfg, params, prompts, max_new=5)

    eng = ContinuousEngine(cfg, params, batch_slots=2, capacity=64)
    # slots=2, three requests: uid 2 is admitted into whichever slot
    # frees first, while the other resident keeps decoding
    for r in _reqs(prompts, max_new=5):
        eng.submit(r)
    done = {r.uid: r.out for r in eng.run(max_steps=300)}
    assert done == solo


def test_slot_churn_no_cache_row_leaks(engine_setup):
    """Property sweep: randomized prompts and Poisson-ish staggered
    arrivals through 3 slots — every request's output equals its solo
    oracle, so no admission/eviction sequence leaks cache rows."""
    cfg, params = engine_setup
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.padded_vocab, rng.randint(1, 5)))
               for _ in range(8)]
    ticks = np.cumsum(rng.randint(0, 4, len(prompts)))
    solo = _solo_outs(cfg, params, prompts, max_new=3)

    eng = ContinuousEngine(cfg, params, batch_slots=3, capacity=64)
    arrivals = [(int(t), r) for t, r in zip(ticks, _reqs(prompts, max_new=3))]
    done = {r.uid: r.out for r in eng.run(max_steps=400, arrivals=arrivals)}
    assert done == solo
    assert all(r is None for r in eng.slot_req)  # fully drained
    # bookkeeping: every request has admit/finish ticks, in order
    for uid in solo:
        assert eng.admit_step[uid] < eng.finish_step[uid]


# -------------------------------------------------------- chunked prefill


def _prompt(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 500, n)]


def _wave(cfg, params, prompts, *, slots=2, capacity=CAP, max_new=4):
    """{uid: tokens} from the wave oracle."""
    wave = ServeEngine(cfg, params, batch_slots=slots, capacity=capacity)
    for r in _reqs(prompts, max_new):
        wave.submit(r)
    return {r.uid: r.out for r in wave.run(max_steps=2000)}


def _continuous(cfg, params, prompts, *, slots=2, capacity=CAP, max_new=4,
                runtime=None):
    """{uid: tokens} from a continuous engine, and its tick record."""
    kw = (dict(runtime=runtime) if runtime is not None
          else dict(batch_slots=slots, capacity=capacity))
    with ContinuousEngine(cfg, params, **kw) as eng:
        for r in _reqs(prompts, max_new):
            eng.submit(r)
        got = {r.uid: r.out for r in eng.run(max_steps=2000)}
    return got, eng.record.snapshot()["ticks"]


def _wave_and_continuous(cfg, params, prompts, **kw):
    """The wave oracle's tokens, the continuous engine's and its record."""
    return (_wave(cfg, params, prompts, **kw),
            *_continuous(cfg, params, prompts, **kw))


@pytest.mark.parametrize("n", [1, C - 1, C, C + 1, 3 * C + 5])
def test_chunked_prefill_matches_wave_engine(engine_setup, n):
    """A prompt of n tokens taken in chunks, beside a batch-mate that
    decodes through its chunks: both outputs equal the wave oracle's, and
    every prompt token went in through a chunk."""
    cfg, params = engine_setup
    prompts = [_prompt(n), [5, 7, 9]]
    want, got, ticks = _wave_and_continuous(cfg, params, prompts)
    assert got == want
    assert sum(ticks["prefill_tokens"]) == n + 3
    assert sum(ticks["replay_tokens"]) == 0
    assert max(ticks["prefill_tokens"]) <= C


def test_chunked_prefill_below_the_chunk_width(engine_setup):
    """At a capacity below C the chunk is the capacity wide; a prompt that
    fills the cache is one chunk."""
    cfg, params = engine_setup
    prompts = [_prompt(32), _prompt(20, 1), [4]]
    want, got, ticks = _wave_and_continuous(cfg, params, prompts,
                                            capacity=32, max_new=1)
    assert got == want
    assert sorted(t for t in ticks["prefill_tokens"] if t) == [1, 20, 32]


def test_prefill_fifo_takes_chunks_in_admission_order(engine_setup):
    """Two long prompts admitted in one tick: the first takes a chunk each
    tick until its prompt is in, then the second; a short one admitted
    with them waits its turn too.  Outputs equal the wave oracle's."""
    cfg, params = engine_setup
    lens = [2 * C + 3, C + 7, 2]
    prompts = [_prompt(n, k) for k, n in enumerate(lens)]
    want, got, ticks = _wave_and_continuous(cfg, params, prompts, slots=3)
    assert got == want
    assert ticks["prefill_tokens"][:6] == [C, C, 3, C, 7, 2]

    eng = ContinuousEngine(cfg, params, batch_slots=3, capacity=CAP)
    for r in _reqs(prompts):
        eng.submit(r)
    firsts = {}
    while len(firsts) < 3:
        k = eng.steps_done
        eng.tick()
        for i, req in enumerate(eng.slot_req):
            if req is not None and req.out and req.uid not in firsts:
                firsts[req.uid] = k
    assert firsts == {0: 2, 1: 4, 2: 5}


@pytest.mark.parametrize("variant", [
    dict(local_window=8),
    dict(pattern=("attn", "ssm"), ssm_state=16, ssm_headdim=16),
], ids=["windowed", "attn-ssm"])
def test_replay_path_where_chunks_cannot_go(engine_setup, variant):
    """The same prompts under a windowed or an attention-plus-SSM config:
    no chunk, every prompt token replayed through a decode row, and the
    outputs equal the wave oracle's."""
    cfg = engine_setup[0].scaled(**variant)
    params = init_lm(jax.random.PRNGKey(0), cfg, ParallelCtx())
    prompts = [_prompt(n) for n in (1, C - 1, C + 1)]
    want, got, ticks = _wave_and_continuous(cfg, params, prompts)
    assert got == want
    assert sum(ticks["prefill_tokens"]) == 0
    assert sum(ticks["replay_tokens"]) == 1 + 2 * C


def test_waiting_slot_gets_no_cache_row(engine_setup):
    """While one slot takes its chunks, another slot waiting for its own
    has no cache row written by the step, and the filling slot holds
    exactly the positions its chunks took in, in every layer."""
    cfg, params = engine_setup
    eng = ContinuousEngine(cfg, params, batch_slots=3, capacity=CAP)
    for r in _reqs([_prompt(3 * C), _prompt(2 * C, 1), [5, 7]]):
        eng.submit(r)
    for k in range(1, 4):
        eng.tick()
        for path, leaf in jax.tree_util.tree_leaves_with_path(eng.caches):
            if "slot_pos" not in jax.tree_util.keystr(path):
                continue
            rows = np.asarray(leaf)           # (layers, slots, capacity)
            assert (rows[:, 1] == -1).all()
            want = np.where(np.arange(CAP) < k * C, np.arange(CAP), -1)
            assert (rows[:, 0] == want).all()
    assert eng.slot_req[1].out == []


# ------------------------------------------------------------- migration


def test_pack_unpack_matches_copy_slot_oracle(engine_setup):
    """unpack(pack(src), dst) == copy_slot(src, dst) leaf-for-leaf: the
    byte image is exact for every cache leaf dtype (bf16 KV, int32
    slot_pos, f32 state)."""
    cfg, params = engine_setup
    caches = lm_caches(cfg, 3, capacity=16, ctx=ParallelCtx())
    # make rows distinguishable: run two decode steps on real data
    eng = ContinuousEngine(cfg, params, batch_slots=3, capacity=16)
    for r in _reqs([[5, 7], [11, 3], [9]], max_new=2):
        eng.submit(r)
    eng.tick()
    eng.tick()
    caches = eng.caches

    want = copy_slot(caches, 0, 2)
    got = unpack_slot(caches, pack_slot(caches, 0), 2)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_migration_preserves_output(engine_setup):
    """Migrating a request to a different slot mid-decode changes nothing
    about its remaining tokens (the image carries cache rows exactly;
    pos/cursor/last-token travel with it)."""
    cfg, params = engine_setup
    prompts = [[5, 7, 9], [11, 3]]
    solo = _solo_outs(cfg, params, prompts, max_new=6)

    eng = ContinuousEngine(cfg, params, batch_slots=3, capacity=64)
    for r in _reqs(prompts, max_new=6):
        eng.submit(r)
    for _ in range(4):
        eng.tick()
    moved = eng.migrate(0, 2)           # uid 0's cache image: slot 0 -> 2
    assert eng.slot_req[2] is moved and eng.slot_req[0] is None
    done = {r.uid: r.out for r in eng.run(max_steps=200)}
    done.update({r.uid: r.out for r in [moved] if r.done})
    assert done == solo


# ---------------------------------------------- tensor-parallel engines


TP_MESHES = {"ring": (1, 8), "torus": (2, 4)}


def _tp_cfg():
    # n_heads=8 divides both tp=8 and tp=4 evenly, so init_lm needs no
    # head padding and single-device params equal the TP layout exactly
    return smoke(get_arch("glm4-9b")).scaled(n_heads=8, d_model=128,
                                             d_ff=128)


@pytest.mark.parametrize("backend", ["static", "packet"])
@pytest.mark.parametrize("dims", list(TP_MESHES.values()),
                         ids=list(TP_MESHES))
def test_tp_continuous_matches_wave_oracle(dims, backend, devices8):
    """The tensor-parallel continuous engine on persistent channels
    produces the same greedy tokens as the single-device wave engine, on
    ring and torus meshes, static and packet backends."""
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_continuous_serve

    cfg = _tp_cfg()
    params = init_lm(jax.random.PRNGKey(0), cfg, ParallelCtx())
    prompts = [[5, 7, 9], [11, 3], [4, 8]]

    wave = ServeEngine(cfg, params, batch_slots=2, capacity=32)
    for r in _reqs(prompts, max_new=3):
        wave.submit(r)
    want = {r.uid: r.out for r in wave.run(max_steps=200)}

    mesh = make_mesh(dims, ("data", "model"))
    rt = build_continuous_serve(cfg, mesh, comm_mode=f"smi:{backend}",
                                batch_slots=2, capacity=32)
    with ContinuousEngine(
        cfg, jax.device_put(params, rt["param_sharding"]), runtime=rt,
    ) as eng:
        for r in _reqs(prompts, max_new=3):
            eng.submit(r)
        got = {r.uid: r.out for r in eng.run(max_steps=200)}
    assert got == want, f"{backend} on {dims} diverged from wave oracle"


@pytest.mark.parametrize("capacity,n", [(2 * C, C + 6), (10 * C, 2 * C + 6)],
                         ids=["shard-below-chunk", "chunk-straddles-shards"])
def test_tp_chunked_prefill_matches_wave_oracle(devices8, capacity, n):
    """On a ring of 8 the sequence-sharded cache takes a prompt longer than
    C in chunks, each shard writing the part in its own rows: shards of
    fewer rows than C, and shards of 1.25 C rows, which a chunk straddles.
    The greedy tokens equal the single-device wave engine's."""
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_continuous_serve

    cfg = _tp_cfg()
    params = init_lm(jax.random.PRNGKey(0), cfg, ParallelCtx())
    prompts = [_prompt(n), [11, 3], _prompt(40, 1)]
    rt = build_continuous_serve(cfg, make_mesh((1, 8), ("data", "model")),
                                comm_mode="smi:static", batch_slots=2,
                                capacity=capacity)
    want = _wave(cfg, params, prompts, capacity=capacity, max_new=3)
    got, ticks = _continuous(cfg, jax.device_put(params, rt["param_sharding"]),
                             prompts, max_new=3, runtime=rt)
    assert got == want
    assert sum(ticks["prefill_tokens"]) == n + 2 + 40


def test_persistent_pool_lifecycle(devices8):
    """The pool's port claims are strong: they survive trace exits and
    gc of the compiled step, and come back ONLY at pool close (engine
    shutdown) — the ChannelSpec(persistent=True) contract."""
    from repro.channels import PORTS
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_continuous_serve

    cfg = _tp_cfg()
    mesh = make_mesh((1, 8), ("data", "model"))
    rt = build_continuous_serve(cfg, mesh, comm_mode="smi:static",
                                batch_slots=2, capacity=32)
    pool, comm = rt["pool"], rt["ctx"].model_comm
    assert pool is not None and not pool.closed

    # trace the decode step: every layer tag claims its persistent port
    pshapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg,
                                             rt["ctx"]))
    cshapes = jax.eval_shape(rt["init_caches"])
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    pos = jax.ShapeDtypeStruct((2,), jnp.int32)
    lowered = rt["step"].lower(pshapes, cshapes, tok, pos)
    ports = pool.ports()
    assert len(ports) > 2  # layer channels + the migration pair
    assert all(tag.startswith("serve.") for tag in ports)
    assert set(ports.values()) <= set(PORTS.in_use(comm))

    # the claim outlives the trace: drop the lowered step, collect, and
    # re-trace — same specs, same ports, nothing lapsed in between
    del lowered
    gc.collect()
    assert set(ports.values()) <= set(PORTS.in_use(comm))
    rt["step"].lower(pshapes, cshapes, tok, pos)
    assert pool.ports() == ports

    pool.close()
    assert pool.closed
    assert not set(ports.values()) & set(PORTS.in_use(comm))


# ------------------------------------- predicted-vs-measured regression


def test_predict_decode_step_stats_matches_ledger(devices8):
    """The serving decode-step predictor equals the traced channel
    ledger to the byte per serve.* tag, migration legs included (the
    ``launch/serve --validate-comm`` contract, DESIGN.md §13)."""
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_continuous_serve
    from repro.netsim import predict_decode_step_stats
    from repro.parallel import ledger

    class St:
        comm_mode = "smi:static"

    cfg = smoke(get_arch("yi-6b"))
    B, cap = 2, 32
    mesh = make_mesh((2, 4), ("data", "model"))
    rt = build_continuous_serve(cfg, mesh, comm_mode=St.comm_mode,
                                batch_slots=B, capacity=cap)
    pshapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg,
                                             rt["ctx"]))
    cshapes = jax.eval_shape(rt["init_caches"])
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    with ledger.capture() as led:
        rt["step"].lower(pshapes, cshapes, tok, pos)
        infl = jax.eval_shape(rt["migrate_start"], cshapes, slot)
        rt["migrate_start"].lower(cshapes, slot)
        rt["migrate_finish"].lower(cshapes, infl, slot)
    rt["pool"].close()
    measured = {t: dict(e) for t, e in led.by_tag.items()}
    predicted = predict_decode_step_stats(cfg, (2, 4), B, St,
                                          capacity=cap, migrations=1)
    assert predicted == measured
