"""Spans and the host-time record of the continuous engine
(serving/continuous.py, serving/record.py, obs/trace.py ``span``,
DESIGN.md §11).

* **record** — per request, submit / admit / first token / finish on the
  host clock; per tick, one sample of each phase; compilations and GC
  pauses counted inside ticks; nothing on the device held.
* **spans** — under ``jax.profiler`` the phase spans land nested inside
  ``serve.tick`` on the clock the record uses; with an obs tracer they
  become ``dur`` events that export as complete slices.
* **stable names** — the engine's programs and the stencil dispatch carry
  fixed names, and the stencil step's regions their named scopes.
"""

import gc
import json
import time
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke
from repro.mesh.api import ParallelCtx
from repro.models import init_lm
from repro.obs import metrics
from repro.obs import trace as obs
from repro.obs.export import to_chrome_trace
from repro.serving import ContinuousEngine, Request
from repro.serving.continuous import PREFILL_CHUNK as C
from repro.serving.record import PHASES

SPANS = ["serve.tick", *(f"serve.{p}" for p in PHASES)]


@pytest.fixture(scope="module")
def engine_setup():
    cfg = smoke(get_arch("yi-6b"))
    params = init_lm(jax.random.PRNGKey(0), cfg, ParallelCtx())
    return cfg, params


def _drain(eng, max_ticks=200):
    for _ in range(max_ticks):
        if not eng.queue and all(r is None for r in eng.slot_req):
            return
        eng.tick()
    raise AssertionError("engine did not drain")


def _warm(cfg, params, slots=2, capacity=32):
    """An engine whose programs have all run once at its shapes."""
    eng = ContinuousEngine(cfg, params, batch_slots=slots, capacity=capacity)
    eng.submit(Request(uid=-1, prompt=[1, 2], max_new=2))
    _drain(eng)
    return eng


# ------------------------------------------------------------- the record


def _first_token_tick(eng, n):
    """Submit a prompt of n tokens, tick until it is served; the tick that
    gave its first token."""
    eng.submit(Request(uid=7, prompt=list(range(3, 3 + n)), max_new=3))
    first_tick = None
    while not eng.finish_step.get(7):
        k = eng.steps_done
        eng.tick()
        req = eng.record.snapshot()["requests"][-1]
        if first_tick is None and req["first"] is not None:
            first_tick = k
    return first_tick


@pytest.mark.parametrize("n", [1, 5, C + 1])
def test_first_token_n_ticks_after_admission(engine_setup, n):
    """A prompt of n tokens goes into the cache C a tick: its first token
    comes out of the ceil(n / C)-th tick counted from the one that
    admitted it, and the record's stamps fall inside those two ticks."""
    cfg, params = engine_setup
    eng = _warm(cfg, params, capacity=2 * C)
    first_tick = _first_token_tick(eng, n)
    assert first_tick - eng.admit_step[7] == -(-n // C) - 1
    snap = eng.record.snapshot()
    req = next(r for r in snap["requests"] if r["uid"] == 7)
    ticks = snap["ticks"]
    start = ticks["start_ns"]

    def tick_of(ns):
        return next(i for i in range(len(start))
                    if start[i] <= ns and (i + 1 == len(start)
                                           or ns < start[i + 1]))

    assert req["submit"] <= req["admit"] < req["first"] <= req["finish"]
    assert tick_of(req["admit"]) == eng.admit_step[7]
    assert tick_of(req["first"]) == first_tick
    # the first token is stamped when the greedy pick reached the host
    i = first_tick
    end_of_sample = start[i] + sum(ticks[f"{p}_ns"][i]
                                   for p in PHASES[:PHASES.index("sample") + 1])
    assert req["first"] == end_of_sample


def test_first_token_n_ticks_after_admission_on_replay(engine_setup):
    """Where chunks cannot go (a windowed config), a prompt of n tokens
    replays one a tick: its first token comes out of the n-th tick."""
    cfg = engine_setup[0].scaled(local_window=8)
    params = init_lm(jax.random.PRNGKey(0), cfg, ParallelCtx())
    eng = _warm(cfg, params)
    n = 5
    assert _first_token_tick(eng, n) - eng.admit_step[7] == n - 1


@pytest.mark.parametrize("window", [None, 8], ids=["chunked", "replay"])
def test_prompt_tokens_counted_once(engine_setup, window):
    """Over a drain, the ticks' ``prefill_tokens`` sum to the prompts'
    lengths on the chunked path, and their ``replay_tokens`` on the replay
    path; the other column reads 0."""
    cfg, params = engine_setup
    if window is not None:
        cfg = cfg.scaled(local_window=window)
        params = init_lm(jax.random.PRNGKey(0), cfg, ParallelCtx())
    eng = ContinuousEngine(cfg, params, batch_slots=2, capacity=2 * C)
    prompts = [list(range(1, 1 + n)) for n in (C + 9, 3, 1, 12)]
    for uid, prompt in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=prompt, max_new=2))
    _drain(eng)
    ticks = eng.record.snapshot()["ticks"]
    total = sum(map(len, prompts))
    taken, other = "prefill_tokens", "replay_tokens"
    if window is not None:
        taken, other = other, taken
    assert sum(ticks[taken]) == total and sum(ticks[other]) == 0
    assert len(ticks[taken]) == len(eng.record)


def test_one_phase_sample_per_tick(engine_setup):
    """Each tick that ran a step adds one sample of every phase; the
    phases are non-negative and sum to no more than the tick took."""
    cfg, params = engine_setup
    eng = ContinuousEngine(cfg, params, batch_slots=2, capacity=32)
    for uid, prompt in enumerate([[5, 7, 9], [11, 3], [4]]):
        eng.submit(Request(uid=uid, prompt=prompt, max_new=3))
    outer = []
    while eng.queue or any(r is not None for r in eng.slot_req):
        t0 = time.time_ns()
        eng.tick()
        outer.append(time.time_ns() - t0)
    ticks = eng.record.snapshot()["ticks"]
    assert len(eng.record) == eng.steps_done == len(outer)
    for k, wall in enumerate(outer):
        phases = [ticks[f"{p}_ns"][k] for p in PHASES]
        assert min(phases) >= 0
        assert sum(phases) <= wall
    assert eng.tick() == [] and len(eng.record) == eng.steps_done


def test_compile_counter_reads_warm_and_fresh_shapes(engine_setup):
    """Warm ticks begin no compilation; the first tick at a fresh batch
    shape begins at least one, and the counter puts it in that tick."""
    cfg, params = engine_setup
    eng = _warm(cfg, params)
    n = len(eng.record)
    eng.submit(Request(uid=0, prompt=[3, 4, 5], max_new=3))
    _drain(eng)
    assert eng.record.snapshot()["ticks"]["compiles"][n:] == [0] * (
        len(eng.record) - n)

    fresh = ContinuousEngine(cfg, params, batch_slots=3, capacity=32)
    fresh.submit(Request(uid=0, prompt=[3], max_new=2))
    fresh.tick()
    assert fresh.record.snapshot()["ticks"]["compiles"][0] >= 1


def test_gc_pause_is_counted():
    """A collection adds its pause to the process counters."""
    rt = metrics.runtime_counters()
    ns = rt.gc_ns
    gc.collect()
    assert rt.gc_ns > ns


def test_record_holds_no_device_array(engine_setup):
    """The registry keeps the record after the engine is gone, and the
    record keeps none of the engine's device buffers alive."""
    cfg, params = engine_setup
    eng = ContinuousEngine(cfg, params, batch_slots=2, capacity=32,
                           name="serve.leak")
    eng.submit(Request(uid=0, prompt=[5, 7], max_new=2))
    _drain(eng)
    record = eng.record
    caches = [weakref.ref(x) for x in jax.tree.leaves(eng.caches)]
    del eng
    gc.collect()
    assert all(r() is None for r in caches)
    assert metrics.REGISTRY.records["serve.leak"] is record
    snap = metrics.REGISTRY.snapshot()["records"]["serve.leak"]
    json.dumps(snap)
    assert not any(isinstance(v, jax.Array) for v in vars(record).values())


def test_new_engine_replaces_the_record(engine_setup):
    cfg, params = engine_setup
    a = ContinuousEngine(cfg, params, batch_slots=1, capacity=16)
    b = ContinuousEngine(cfg, params, batch_slots=1, capacity=16)
    assert metrics.REGISTRY.records["serve"] is b.record is not a.record


# ------------------------------------------------------------- the spans


def _xplane_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    prof = ProfileData.from_file(str(path))
    t0 = None
    spans = []
    for plane in prof.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            t0 = stats["profile_start_time"]
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith("serve."))
    return t0, spans


def test_spans_nest_inside_the_tick_on_the_host_clock(engine_setup, tmp_path):
    """Under the profiler each phase span lies inside a ``serve.tick``
    span, and ``profile_start_time + start_ns`` lies between host stamps
    taken around the ticks, within a millisecond of the record's tick
    start (on the CPU they lie 8-20 us apart)."""
    cfg, params = engine_setup
    eng = _warm(cfg, params)
    eng.submit(Request(uid=0, prompt=[5, 7], max_new=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.002)
        before = time.time_ns()
        n = len(eng.record)
        for _ in range(3):
            eng.tick()
        after = time.time_ns()
        time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    t0, spans = _xplane_spans(tmp_path)
    assert t0 is not None
    ticks = sorted((s, e) for name, s, e in spans if name == "serve.tick")
    assert len(ticks) == 3
    for name in SPANS[1:]:
        mine = [(s, e) for nm, s, e in spans if nm == name]
        assert len(mine) == 3, name
        for s, e in mine:
            assert any(a <= s and e <= b for a, b in ticks), name
    assert any(nm == "serve.reset" for nm, _, _ in spans)
    for s, e in ticks:
        assert before <= t0 + s and t0 + e <= after
    # the record's tick starts sit on the profiler's clock
    starts = eng.record.snapshot()["ticks"]["start_ns"][n:]
    for (s, _), mine in zip(ticks, starts):
        assert abs(t0 + s - mine) < 1_000_000


def test_spans_become_dur_events_with_a_tracer(engine_setup):
    """With an obs tracer on, each span is one schema event with
    ``attrs["dur"]``, rendered as a complete slice; a request's reset
    carries its uid."""
    cfg, params = engine_setup
    eng = _warm(cfg, params)
    eng.submit(Request(uid=42, prompt=[5], max_new=2))
    with obs.enabled() as tr:
        eng.tick()
    events = tr.events()
    kinds = [e["kind"] for e in events]
    for name in SPANS + ["serve.reset"]:
        assert name in kinds, name
    reset = next(e for e in events if e["kind"] == "serve.reset")
    assert reset["attrs"]["uid"] == 42 and reset["attrs"]["slot"] == 0
    tick = next(e for e in events if e["kind"] == "serve.tick")
    for e in events:
        assert e["attrs"]["dur"] >= 0
        assert tick["ts"] <= e["ts"] <= tick["ts"] + tick["attrs"]["dur"]
    doc = to_chrome_trace(events)
    slices = [r for r in doc["traceEvents"] if r.get("ph") == "X"]
    assert len(slices) == len(events)


def test_span_without_tracer_records_nothing():
    assert obs.TRACING is False
    with obs.span("serve.tick", step=1) as sp:
        pass
    assert sp.name == "serve.tick" and sp.ids == {"step": 1}
    with obs.enabled() as tr:
        with obs.span("x.y", uid=3):
            pass
    (ev,) = tr.events()
    assert ev["kind"] == "x.y" and ev["attrs"]["uid"] == 3


# --------------------------------------------------------- stable names


def _module_name(lowered):
    return lowered.as_text().split("module @", 1)[1].split(" ", 1)[0]


def test_engine_programs_have_stable_names(engine_setup):
    """The decode step, the mixed step, the slot reset and the greedy pick
    lower to fixed module names, on the single-device path and the
    runtime one."""
    from repro.launch.steps import build_continuous_serve
    from repro.serving.continuous import serve_greedy

    cfg, params = engine_setup
    eng = ContinuousEngine(cfg, params, batch_slots=2, capacity=16)
    cur = jnp.zeros(2, jnp.int32)
    chunk = (jnp.zeros(eng.chunk, jnp.int32), np.int32(0), np.int32(0),
             np.int32(1))
    assert _module_name(eng._step.lower(params, eng.caches, cur, cur)) \
        == "jit_serve_decode_step"
    assert _module_name(eng._mixed.lower(params, eng.caches, cur, cur,
                                         *chunk)) == "jit_serve_mixed_step"
    assert _module_name(eng._reset.lower(eng.caches, np.int32(0))) \
        == "jit_serve_reset_slot"
    assert _module_name(serve_greedy.lower(jnp.zeros((2, 8)))) \
        == "jit_serve_greedy"

    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    rt = build_continuous_serve(cfg, mesh, comm_mode="smi:static",
                                batch_slots=2, capacity=16)
    caches = rt["init_caches"]()
    assert _module_name(rt["step"].lower(params, caches, cur, cur)) \
        == "jit_serve_decode_step"
    assert _module_name(rt["mixed_step"].lower(params, caches, cur, cur,
                                               *chunk)) \
        == "jit_serve_mixed_step"
    assert _module_name(rt["reset"].lower(caches, np.int32(0))) \
        == "jit_serve_reset_slot"


def test_stencil_program_carries_its_scopes(devices8):
    """The 2x2 stencil dispatch is ``jit_stencil_dispatch`` and its step's
    four regions carry their named scopes into the compiled op
    metadata."""
    from repro.apps import DistributedStencil

    app = DistributedStencil.create((2, 2), comm_mode="smi:static")
    f = app.jitted(app.make_mesh(), n_steps=2, overlapped=True)
    lowered = f.lower(jax.ShapeDtypeStruct((4, 16, 16), jnp.float32))
    assert _module_name(lowered) == "jit_stencil_dispatch"
    text = lowered.compile().as_text()
    for scope in ("halo.start", "stencil.interior", "halo.finish",
                  "stencil.assemble"):
        assert f"/{scope}/" in text, scope
