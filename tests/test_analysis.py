"""smilint: the static channel-program verifier (DESIGN.md §14).

Covers both passes end to end — capture-mode abstract interpretation
(ledger recording, zero real comm, the SMI10x rules) and the AST source
lints (SMI00x, suppression comments, the check_no_stream_shims shim) —
plus the claims-introspection surfaces (PortAllocator / ChannelPool) and
the golden-rule corpus gate that CI enforces.
"""

import gc
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import CATALOG, Diagnostic, ProgramBuilder, verify_program
from repro.analysis import capture as cap
from repro.analysis.corpus import corpus
from repro.analysis.rules import (
    ALL_RULES,
    NoStreamShims,
    lint_paths,
    lint_source,
)
from repro.analysis.verify import verify_ledger
from repro.channels import (
    ChannelPool,
    open_allreduce_channel,
    open_channel,
)
from repro.core import Communicator, PortAllocator, make_test_mesh, pvary, run_spmd
from repro.obs import trace as obs
from repro.transport import get_transport

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def ring8():
    mesh = make_test_mesh((8,), ("x",))
    comm = Communicator.create("x", (8,))
    return mesh, comm


# ---------------------------------------------------------------------------
# capture mode: abstract interpretation of real channel programs
# ---------------------------------------------------------------------------


def _pipeline_prog(comm, mesh, *, count=4, port=0):
    """A claimed p2p push/pop pipeline + an anonymous bcast transfer."""

    def fn(v):
        with open_channel(comm, count=count, src=0, dst=3, port=port,
                          elem_shape=(), dtype=jnp.float32) as ch:
            acc = pvary(jnp.float32(0), comm)  # a loop carry that pops

            def body(i, carry):
                ch, acc = carry
                ch = ch.push(v[0, 0] + i.astype(jnp.float32))
                ch, val, ok = ch.pop()
                return ch, acc + jnp.where(ok, val, 0.0)

            ch, acc = jax.lax.fori_loop(0, count + 2, body, (ch, acc))
        y = open_allreduce_channel(comm, port=None).transfer(
            acc[None] + v[0])
        return y[None]

    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P("x"), out_specs=P("x")))


def test_capture_records_ops_and_moves_no_bytes(ring8):
    mesh, comm = ring8
    f = _pipeline_prog(comm, mesh)
    with cap.capture() as led:
        f.lower(jax.ShapeDtypeStruct((8, 4), jnp.float32))
    assert not cap.ACTIVE and cap.LEDGER is None  # scope restored
    counts = led.counts()
    # fori_loop bodies trace once: one push + one pop in the ledger
    assert counts["open"] == 2
    assert counts["push"] == 1 and counts["pop"] == 1
    assert counts["close"] == 1 and counts["transfer"] == 1
    # the acceptance bar: abstract interpretation executes no collective
    assert led.real_steps == 0
    assert led.transport_steps  # ...but the abstract tallies accrued
    assert all(v["steps"] > 0 for v in led.transport_steps.values())
    opens = [o for o in led.ops if o.op == "open"]
    assert [(o.kind, o.port) for o in opens] == [("p2p", 0),
                                                ("allreduce", None)]
    xfer = next(o for o in led.ops if o.op == "transfer")
    assert xfer.kind == "allreduce" and xfer.port is None
    pushed = next(o for o in led.ops if o.op == "push")
    assert pushed.location and ":" in pushed.location
    assert verify_ledger(led, name="pipeline") == []


def test_capture_is_invisible_to_real_execution(ring8):
    """The same program runs for real before and after a capture — the
    spec's transport cache must never leak the abstract backend out (or a
    real one in)."""
    mesh, comm = ring8
    t = get_transport("static")
    before = t.stats.steps

    def fn(v):
        return open_channel(comm, src=0, dst=3, port=None, transport=t,
                            n_chunks=2).transfer(v[0])[None]

    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    y0 = run_spmd(fn, mesh, P("x"), P("x"), x)
    real_steps_per_run = t.stats.steps - before
    assert real_steps_per_run > 0
    with cap.capture() as led:
        jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))).lower(
            jax.ShapeDtypeStruct((8, 4), jnp.float32))
    assert led.real_steps == 0
    # fresh jit entry post-capture: must resolve the REAL backend again
    y1 = jax.jit(jax.shard_map(
        lambda v: fn(v), mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(y1[3]), np.asarray(x[0]))


def test_capture_flags_port_collision_in_one_trace(ring8):
    """Two live claims on one (comm, port) inside a single traced program
    — the paper's one-port-one-FIFO rule — surfaces as SMI101."""
    mesh, comm = ring8
    pa = PortAllocator()

    def fn(v):
        a = open_channel(comm, src=0, dst=1, port=3, allocator=pa)
        b = open_channel(comm, src=0, dst=2, port=3, allocator=pa)
        return (v + 0 * (a.pipe + b.pipe))[:1]

    with pytest.raises(ValueError, match="already claimed"):
        with cap.capture():
            jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))).lower(
                jax.ShapeDtypeStruct((8,), jnp.float32))


def test_verifier_reports_seeded_collision():
    b = ProgramBuilder(size=2)
    s = b.spmd()
    s.open(kind="p2p", port=3, src=0, dst=1, count=1, dtype="float32")
    s.open(kind="p2p", port=3, src=0, dst=1, count=1, dtype="float32")
    diags = verify_program(b.build("seeded"))
    assert any(d.rule == "SMI101" for d in diags)
    d = next(d for d in diags if d.rule == "SMI101")
    row = d.to_dict()
    assert row["port"] == 3 and row["severity"] == CATALOG["SMI101"][0]


# ---------------------------------------------------------------------------
# the in-repo program sweep (the CI capture gate, acceptance criteria)
# ---------------------------------------------------------------------------


def test_capture_train_program_is_clean_and_executes_no_collective():
    from repro.analysis.programs import capture_train

    led = capture_train()
    assert led.real_steps == 0, "capture-mode train lowering moved bytes"
    assert led.transport_steps, "train lowered without any channel traffic"
    assert verify_ledger(led, name="launch.train") == []


def test_capture_serve_program_is_clean_and_executes_no_collective():
    from repro.analysis.programs import capture_serve

    led = capture_serve()
    assert led.real_steps == 0, "capture-mode serve lowering moved bytes"
    counts = led.counts()
    # the pool's persistent claims balance: opened AND closed in-capture
    assert counts.get("pool.open", 0) >= 1
    assert counts.get("pool.open") == counts.get("pool.close")
    assert verify_ledger(led, name="launch.serve") == []


# ---------------------------------------------------------------------------
# corpus: every seeded defect must report exactly its golden rules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", corpus(), ids=lambda c: c.name)
def test_corpus_case_reports_exact_golden_rules(case):
    reported = case.reported()
    assert reported == case.golden, (
        f"{case.name}: reported {sorted(reported)} != "
        f"golden {sorted(case.golden)} ({case.note})")


def test_catalog_covers_every_golden_rule():
    for case in corpus():
        for rule in case.golden:
            assert rule in CATALOG
    assert {r.rule_id for r in ALL_RULES} == {
        r for r in CATALOG if r.startswith("SMI0")}


# ---------------------------------------------------------------------------
# AST pass: repo hygiene + suppression + the legacy shim entry point
# ---------------------------------------------------------------------------


def test_repo_is_smilint_clean():
    assert lint_paths(str(ROOT)) == []


def test_suppression_comment_silences_exactly_the_named_rule():
    src = "y = stream_bcast(x, comm)  # smilint: ignore[SMI001]\n"
    assert lint_source(src, relpath="src/repro/seeded.py") == []
    noisy = lint_source("y = stream_bcast(x, comm)\n",
                        relpath="src/repro/seeded.py")
    assert [d.rule for d in noisy] == ["SMI001"]
    # suppressing a DIFFERENT rule must not silence SMI001
    other = lint_source(
        "y = stream_bcast(x, comm)  # smilint: ignore[SMI004]\n",
        relpath="src/repro/seeded.py")
    assert [d.rule for d in other] == ["SMI001"]


def test_close_discipline_accepts_escapes_and_with():
    clean = (
        "def mk(comm):\n"
        "    ch = open_channel(comm, port=1)\n"
        "    return ch\n"
        "def use(comm, x):\n"
        "    with open_channel(comm, port=2) as ch:\n"
        "        pass\n"
        "    anon = open_channel(comm, port=None)\n"
        "    ch2 = open_channel(comm, port=3)\n"
        "    ch2.close()\n"
    )
    assert lint_source(clean, relpath="src/repro/seeded.py") == []


def test_shim_script_regression(tmp_path):
    """scripts/check_no_stream_shims.py now fronts rule SMI001: clean on
    the repo, exit 1 (naming the file) on a seeded violation."""
    env_ok = subprocess.run(
        [sys.executable, str(ROOT / "scripts/check_no_stream_shims.py")],
        capture_output=True, text=True)
    assert env_ok.returncode == 0, env_ok.stdout + env_ok.stderr
    bad = tmp_path / "src" / "repro"
    bad.mkdir(parents=True)
    (bad / "bad.py").write_text("y = stream_bcast(x, comm, root=0)\n")
    env_bad = subprocess.run(
        [sys.executable, str(ROOT / "scripts/check_no_stream_shims.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert env_bad.returncode == 1
    assert "SMI001" in env_bad.stdout and "bad.py" in env_bad.stdout


# ---------------------------------------------------------------------------
# claims introspection (PortAllocator / ChannelPool)
# ---------------------------------------------------------------------------


def test_port_allocator_claims_rows(ring8):
    _, comm = ring8
    pa = PortAllocator()
    ch = open_channel(comm, src=0, dst=1, port=5, tag="t.claimed",
                      allocator=pa)
    anon = open_channel(comm, src=0, dst=2, port=None, allocator=pa)
    rows = pa.claims(comm)
    assert [r["port"] for r in rows] == [5, None]
    named, anon_row = rows
    assert named["tag"] == "t.claimed" and named["kind"] == "p2p"
    assert not named["anonymous"] and not named["persistent"]
    assert anon_row["anonymous"] and anon_row["kind"] == "p2p"
    ch.close()
    assert [r["port"] for r in pa.claims(comm)] == [None]
    del anon, rows, named, anon_row  # rows hold the owner spec strongly
    gc.collect()
    assert pa.claims(comm) == ()


def test_channel_pool_claims_and_idempotent_close(ring8):
    _, comm = ring8
    pa = PortAllocator()
    pool = ChannelPool(comm, allocator=pa)
    pool.spec("decode.mlp")
    pool.spec("decode.attn", kind="allreduce")
    rows = pool.claims()
    assert [r["port"] for r in rows] == [100, 101]
    assert all(r["persistent"] for r in rows)
    assert rows[0]["tag"] == "serve.decode.mlp"
    # another client's claim on the same allocator stays out of the view
    other = open_channel(comm, src=0, dst=1, port=7, allocator=pa)
    assert [r["port"] for r in pool.claims()] == [100, 101]
    pool.close()
    assert pool.claims() == ()
    pool.close()  # idempotent: a second close is a no-op, not an error
    assert pa.in_use(comm) == (7,)
    other.close()


def test_leaked_pool_emits_ft_leak_and_recovers_ports(ring8):
    _, comm = ring8
    pa = PortAllocator()
    pool = ChannelPool(comm, allocator=pa)
    pool.spec("decode.mlp")
    pool.spec("decode.attn")
    with obs.enabled(capacity=256) as tracer:
        del pool
        gc.collect()
        leaks = [e for e in tracer.events() if e["kind"] == "ft.leak"]
    assert len(leaks) == 1
    assert leaks[0]["attrs"]["ports"] == [100, 101]
    assert leaks[0]["attrs"]["n_claims"] == 2
    assert pa.in_use(comm) == ()  # __del__ recovered the claims
    # a CLOSED pool going out of scope is not a leak
    pool2 = ChannelPool(comm, allocator=pa)
    pool2.spec("decode.mlp")
    pool2.close()
    with obs.enabled(capacity=256) as tracer:
        del pool2
        gc.collect()
        assert [e for e in tracer.events() if e["kind"] == "ft.leak"] == []


# ---------------------------------------------------------------------------
# persistent claims: survival across del + gc (the serving lifecycle)
# ---------------------------------------------------------------------------


def test_persistent_claim_survives_del_and_gc(ring8):
    _, comm = ring8
    pa = PortAllocator()
    pool = ChannelPool(comm, allocator=pa)
    spec = pool.spec("decode.mlp")
    assert pa.in_use(comm) == (100,)
    # every compiled step that used the spec dies; the claim must not
    del spec
    gc.collect()
    assert pa.in_use(comm) == (100,)
    with pytest.raises(ValueError):
        pa.claim(comm, 100)
    pool.close()
    assert pa.in_use(comm) == ()


def test_diagnostic_str_carries_machine_fields():
    d = Diagnostic(rule="SMI104", message="window overrun",
                   rank=1, port=3, tag="tp.col", location="src/x.py:9")
    s = str(d)
    assert "SMI104" in s and "src/x.py:9" in s
    row = d.to_dict()
    assert row["rank"] == 1 and row["port"] == 3 and row["tag"] == "tp.col"
