"""Driver for continuous-batching serving: ``ContinuousEngine.tick``.

Traffic keys: ``slots``, ``capacity``, ``mesh``, ``comm_mode``,
``rate_per_s``, ``prompt`` and ``output`` (clipped lognormals: ``median``,
``sigma``, ``min``, ``max``), ``max_total`` (prompt plus output),
``checked_requests``, ``drain_limit_s`` and ``trace_seconds``.  Requests arrive on an open loop (``bench/requests.py``)
and are submitted before the first tick that starts after they are due.
Every request due in the window is followed to its last token: arrivals go
on after the window so that the load stays, and the run ends when the last
of the window's requests has finished, or ``drain_limit_s`` after the
window closed.  A request still unfinished then counts as failed, with its
time to first token (if it has none) taken up to that moment.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, requests, seeds, weights
from bench.harness import Check, Measured
from bench.references import llama

#: the widest gap, over every checked token, by which the served token's
#: logit lies below the reference's best (PERF.md gives the readings)
MAX_LOGIT_GAP = 0.12


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


class Driver:
    def __init__(self, cell, *, seed: int, devices, seconds: float):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.t = cell.traffic
        self.c = cell.config
        self.seconds = seconds

    def build(self, cfg, mesh):
        """The engine's runtime: the compiled decode step and its caches."""
        from repro.launch.steps import build_continuous_serve

        return build_continuous_serve(
            cfg, mesh, comm_mode=self.t["comm_mode"],
            batch_slots=self.t["slots"], capacity=self.t["capacity"])

    def engine(self):
        from repro.serving import ContinuousEngine

        return ContinuousEngine(self.cfg, self.params, runtime=self.rt)

    def setup(self) -> None:
        self.cfg = program.model_config(self.c)
        self.rt = self.build(self.cfg, program.mesh(self.t["mesh"]))
        shapes = program.param_shapes(self.cfg, self.rt["ctx"])
        self.params = weights.make(shapes, seeds.jax_key(self.seed, "weights"),
                                   self.c["vocab_size"],
                                   self.rt["param_sharding"])
        # warm every program the window runs: admission (slot reset), the
        # decode step, the greedy pick, the host transfers
        from repro.serving import Request

        eng = self.engine()
        eng.submit(Request(uid=-1, prompt=[1, 2], max_new=2))
        while not eng.tick():
            pass
        eng.shutdown()
        del eng
        self.plan = requests.plan(self.t, self.c["vocab_size"], self.seed,
                                  self.seconds)
        self.eng = self.engine()

    def measure(self, win) -> Measured:
        from repro.serving import Request

        plan, eng = self.plan, self.eng
        live: dict[int, tuple] = {}       # uid -> (Planned, Request)
        tick_start: list[float] = []     # host time of each engine tick
        tick_live_kv: list[int] = []     # KV rows held by live requests
        tick_active: list[int] = []      # requests decoding in each tick
        nxt, left = 0, sum(p.in_window for p in plan)
        deadline = None
        win.start()
        while left:
            now = win.now
            if deadline is None and now >= win.seconds:
                win.end_trace()
                deadline = now + float(self.t["drain_limit_s"])
            if deadline is not None and now >= deadline:
                break
            while nxt < len(plan) and plan[nxt].due <= now:
                p = plan[nxt]
                p.submitted = now
                r = Request(uid=p.uid, prompt=p.prompt, max_new=p.max_new)
                eng.submit(r)
                live[p.uid] = (p, r)
                nxt += 1
            if not live:
                time.sleep(max(0.0, min(plan[nxt].due, win.seconds) - now)
                           if nxt < len(plan) else 0.0)
                continue
            win.tick()
            k = eng.steps_done
            tick_start.append(win.now)
            with jax.profiler.TraceAnnotation("bench.tick"):
                eng.tick()
            t_done = win.now
            kv = active = 0
            for uid in list(live):
                p, r = live[uid]
                if uid in eng.admit_step:
                    kv += k - eng.admit_step[uid] + 1
                    active += 1
                while len(p.token_times) < len(r.out):
                    p.token_times.append(t_done)
                if r.done:
                    p.out = list(r.out)
                    del live[uid]
                    left -= p.in_window
            tick_live_kv.append(kv)
            tick_active.append(active)
        win.stop()
        end = win.elapsed
        self.admit = {uid: tick_start[s] for uid, s in eng.admit_step.items()}
        eng.shutdown()
        del eng, self.eng

        mine = [p for p in plan if p.in_window]
        failed = [p for p in mine if len(p.out) < p.max_new]
        ttft = [(p.token_times[0] if p.token_times else end) - p.due
                for p in mine]
        itl = [b - a for p in mine
               for a, b in zip(p.token_times, p.token_times[1:])]
        wait = [(self.admit.get(p.uid, end)) - p.due for p in mine]
        self.elapsed, self.ticks = end, len(tick_start)
        return Measured(
            metrics={"ttft_p95_ms": 1e3 * p95(ttft),
                     "itl_p95_ms": 1e3 * p95(itl) if itl else float("nan")},
            attempted=len(mine), failed=len(failed),
            stats={"queue_wait_s": wait, "traced": win.traced,
                   "tick_live_kv": tick_live_kv, "tick_active": tick_active})

    def release(self) -> None:
        """Free the engine's runtime; the weights stay for the reference."""
        del self.rt

    def checked(self) -> list:
        """The window's finished requests that the reference recomputes: the
        one with the most tokens, and others drawn from the seed."""
        done = [p for p in self.plan if p.in_window and len(p.out) == p.max_new]
        if not done:
            return []
        longest = max(done, key=lambda p: len(p.prompt) + p.max_new)
        rest = [p for p in done if p is not longest]
        rng = seeds.numpy_rng(self.seed, "serve.checked")
        k = min(len(rest), int(self.t["checked_requests"]) - 1)
        pick = [rest[i] for i in rng.choice(len(rest), k, replace=False)]
        return [longest] + pick

    def check(self) -> list[Check]:
        return [Check("max_logit_gap",
                      logit_gap(self.params, self.checked(), self.c,
                                self.t["capacity"]),
                      MAX_LOGIT_GAP)]


def reference_logits(params, p, cfg, length, quantize=False):
    """Reference logits at the positions that predict each served token of
    planned request ``p``; the sequence is padded to ``length`` (the cache's
    capacity, so that one compiled reference serves every request), which a
    causal mask leaves without effect on earlier positions."""
    seq = p.prompt + p.out[:-1]
    n = len(seq)
    lg = llama.logits(params, seq + [0] * (length - n), cfg, quantize)
    return lg[len(p.prompt) - 1:n]


def logit_gap(params, checked, cfg, length) -> float:
    """Widest gap between the reference's best logit and the served token's
    logit, over every served token of the checked requests."""
    if not checked:
        return float("inf")
    worst = 0.0
    for p in checked:
        lg = reference_logits(params, p, cfg, length)
        tok = np.asarray(p.out)
        if tok.min() < 0 or tok.max() >= lg.shape[1]:
            return float("inf")
        got = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], 1)[:, 0]
        worst = max(worst, float(jnp.max(jnp.max(lg, 1) - got)))
    return worst


def summary(drv) -> dict:
    """What a rate sweep reads: how long the run took and how the queue
    fared."""
    mine = [p for p in drv.plan if p.in_window]
    wait = [drv.admit.get(p.uid, drv.elapsed) - p.due for p in mine]
    return {"elapsed_s": drv.elapsed, "ticks": drv.ticks,
            "tick_ms": 1e3 * drv.elapsed / max(drv.ticks, 1),
            "queue_wait_p95_ms": 1e3 * p95(wait),
            "queue_wait_max_ms": 1e3 * max(wait),
            "submit_late_p95_ms": 1e3 * p95([p.submitted - p.due for p in mine]),
            "tokens": sum(len(p.out) + len(p.prompt) for p in mine)}


def control(drv) -> float:
    """The control's reading of ``max_logit_gap``: the reference in float8
    in the program's place.  At each position of the checked requests' own
    prompts and served tokens, the gap (in the float32 reference) of the
    token that the float8 reference puts first."""
    checked = drv.checked()
    if not checked:
        return float("inf")
    worst = 0.0
    length = drv.t["capacity"]
    for p in checked:
        lg = reference_logits(drv.params, p, drv.c, length)
        pick = jnp.argmax(reference_logits(drv.params, p, drv.c, length, True), 1)
        got = jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
        worst = max(worst, float(jnp.max(jnp.max(lg, 1) - got)))
    return worst
