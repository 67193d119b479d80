"""``serve.chunked_prompt_share``: the share of prompt tokens the measured
engine took in prompt chunks, from its record's ``prefill_tokens`` and
``replay_tokens`` columns; None where the record lacks them (an engine
that replays every prompt and does not count) or holds no prompt token."""

import pytest

from bench import engine_record
from bench.tests.test_correctness import run
from bench.tests.test_serve_readers import Record, read, synthetic

NAME = "serve.chunked_prompt_share"


@pytest.fixture
def registry(monkeypatch):
    from repro.obs.metrics import REGISTRY

    monkeypatch.setattr(REGISTRY, "records", {})
    return REGISTRY


def counted(prefill, replay) -> dict:
    snap = synthetic()
    n = len(snap["ticks"]["start_ns"])
    snap["ticks"]["prefill_tokens"] = [prefill] * n
    snap["ticks"]["replay_tokens"] = [replay] * n
    return snap


@pytest.mark.parametrize("prefill,replay,want", [
    (64, 0, 100.0),
    (0, 3, 0.0),
    (48, 16, 75.0),
])
def test_share_on_a_synthetic_record(registry, prefill, replay, want):
    registry.publish(engine_record.ENGINE, Record(counted(prefill, replay)))
    assert read(NAME) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("snap", [synthetic(), counted(0, 0)],
                         ids=["without the columns", "no prompt token"])
def test_share_reads_nothing(registry, snap):
    assert read(NAME) is None
    registry.publish(engine_record.ENGINE, Record(snap))
    assert read(NAME) is None


def test_serve_cell_takes_every_prompt_in_chunks(registry):
    """A small serve run on the CPU: every prompt fits in the cache, so the
    measured engine takes all of them in chunks."""
    run("yi6b-serve-chat")
    assert read(NAME) == 100.0
