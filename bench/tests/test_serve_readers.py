"""The per-layer metrics that read the serving engine's own record
(``bench/engine_record.py``): each gives its p95, mean or count on a
synthetic record, None where the program publishes none, and after a run
of the serve cell on the CPU it reads the measured engine, not the
warm-up's."""

import numpy as np
import pytest

from bench import engine_record, harness
from bench.tests.test_correctness import run

MS = 1_000_000
READERS = ("serve.prefill_p95_ms", "serve.tick_host_ms", "serve.tick_compiles")


class Record:
    def __init__(self, snap):
        self.snap = snap

    def snapshot(self):
        return self.snap


def synthetic() -> dict:
    """60 ticks of 10 ms, 12 ms apart, with a 1 s pause between ticks 20
    and 21.  Requests 0..19 are due in the window: request i takes its slot
    in tick i and gets its first token at the end of the sample phase of
    tick 2i, so its engine time is 10 i + 8.5 ms whether or not the pause
    falls inside; request 20 is due in the window but still replaying its
    prompt, and two later requests (not due in the window) waited long."""
    n = 60
    start = [12 * MS * k + (1000 * MS if k > 20 else 0) for k in range(n)]
    ticks = {"start_ns": start,
             "admit_ns": [MS if k % 2 == 0 else 0 for k in range(n)],
             "prepare_ns": [0] * n,
             "dispatch_ns": [MS] * n,
             "sample_ns": [7 * MS if k % 2 == 0 else 8 * MS for k in range(n)],
             "harvest_ns": [MS] * n,
             "compiles": [3] + [0] * 4 + [1] + [0] * (n - 6),
             "gc_ns": [0] * n}
    requests = [{"uid": i, "submit": start[i], "admit": start[i] + MS // 2,
                 "first": start[2 * i] + 9 * MS, "finish": None}
                for i in range(20)]
    requests.append({"uid": 20, "submit": start[20], "admit": start[20] + 1,
                     "first": None, "finish": None})
    requests += [{"uid": 21 + i, "submit": start[21], "admit": start[21] + 1,
                  "first": start[50], "finish": None} for i in range(2)]
    return {"requests": requests, "ticks": ticks}


#: one wait for each request due in the window, as the serve cell's measurement keeps
STATS = {"queue_wait_s": [0.0] * 21}


def read(name, stats=STATS):
    ctx = harness.LayerContext(cell=None, peaks={}, stats=stats, trace=None,
                               chips=1)
    return harness.read_layer_metric(name, ctx)


@pytest.fixture
def registry(monkeypatch):
    from repro.obs.metrics import REGISTRY

    monkeypatch.setattr(REGISTRY, "records", {})
    return REGISTRY


@pytest.mark.parametrize("name,want", [
    # engine time 10 i + 8.5 ms over i = 0..19, p95 linear between order
    # statistics; the pause, request 20 and the later requests left out
    ("serve.prefill_p95_ms", 10 * float(np.percentile(np.arange(20), 95)) + 8.5),
    # admit + prepare + dispatch + harvest: 3 ms on even ticks, 2 on odd
    ("serve.tick_host_ms", 2.5),
    ("serve.tick_compiles", 4.0),
])
def test_reader_on_a_synthetic_record(registry, name, want):
    registry.publish(engine_record.ENGINE, Record(synthetic()))
    assert read(name) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_record_reads_nothing(registry, name):
    assert read(name) is None
    registry.publish("another engine", Record(synthetic()))
    assert read(name) is None


def test_readers_read_the_measured_engine(registry):
    """A small serve run on the CPU: the record is the measured engine's
    (the warm-up's request is not in it) and holds every request due in the
    window first, no tick compiled, and the engine's prefill p95 lies below
    the time to first token's."""
    line, drv = run("yi6b-serve-chat")
    snap = engine_record.snapshot()
    mine = [p.uid for p in drv.plan if p.in_window]
    assert [r["uid"] for r in snap["requests"][:len(mine)]] == mine
    stats = {"queue_wait_s": [0.0] * len(mine)}
    assert read("serve.tick_compiles", stats) == 0.0
    assert 0 < read("serve.tick_host_ms", stats)
    ttft = [p.token_times[0] - p.due for p in drv.plan if p.in_window]
    prefill = read("serve.prefill_p95_ms", stats)
    assert 0 < prefill < 1e3 * float(np.percentile(ttft, 95))
