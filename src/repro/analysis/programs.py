"""The in-repo programs smilint's capture pass sweeps (DESIGN.md §14).

Each entry traces one real program of the repo — the training step, the
continuous-serving decode step + slot migration, the distributed stencil,
and channel-API programs in the shape of the benchmarks and the
quickstart example — under :func:`repro.analysis.capture`, then verifies
the recorded ledger.  CI gates every entry on **zero diagnostics** and
**zero real transport steps** (abstract interpretation moved no bytes).

Imports the launch stack, so this module (unlike the package root) needs
jax and 8 host devices; the CLI sets ``XLA_FLAGS`` before importing it.
"""

from __future__ import annotations

from . import capture as _capture
from .verify import verify_ledger


def _mesh(dims, axes=("data", "model")):
    from ..launch.mesh import make_mesh

    return make_mesh(dims, axes[: len(dims)])


def capture_train(dims=(2, 4), comm_mode: str = "smi:static"):
    """One smoke training step (the validate-comm recipe, captured)."""
    import jax

    from ..configs import get_arch, smoke
    from ..configs.base import ShapeConfig
    from ..launch.steps import TrainSettings, build_train

    cfg = smoke(get_arch("yi-6b"))
    shape = ShapeConfig("smilint", seq_len=128, global_batch=8, kind="train")
    settings = TrainSettings(comm_mode=comm_mode, remat="nothing",
                             base_lr=3e-4, loss_chunks=1, total_steps=10,
                             warmup_steps=1)
    mesh = _mesh(dims)
    with _capture.capture() as led:
        art = build_train(cfg, mesh, shape, settings)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in art["input_specs"].items()}
        art["step"].lower(art["state_shape"], batch)
    return led


def capture_serve(dims=(2, 4), comm_mode: str = "smi:static"):
    """One continuous decode step + one slot migration over the
    persistent serve.* channel pool, captured; the pool closes inside the
    block so its claims balance (no SMI105)."""
    import jax
    import jax.numpy as jnp

    from ..configs import get_arch, smoke
    from ..launch.steps import build_continuous_serve
    from ..models import init_lm

    cfg = smoke(get_arch("glm4-9b"))
    mesh = _mesh(dims)
    tp = dims[-1]
    with _capture.capture() as led:
        rt = build_continuous_serve(cfg, mesh, comm_mode=comm_mode,
                                    batch_slots=2, capacity=64)
        ctx = rt["ctx"]
        B = rt["batch_slots"]
        pshapes = jax.eval_shape(
            lambda: init_lm(jax.random.PRNGKey(0), cfg, ctx))
        cshapes = jax.eval_shape(rt["init_caches"])
        tok = jax.ShapeDtypeStruct(
            (B, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        slot = jax.ShapeDtypeStruct((), jnp.int32)
        rt["step"].lower(pshapes, cshapes, tok, pos)
        if tp > 1:
            infl = jax.eval_shape(rt["migrate_start"], cshapes, slot)
            rt["migrate_start"].lower(cshapes, slot)
            rt["migrate_finish"].lower(cshapes, infl, slot)
        if rt["pool"] is not None:
            rt["pool"].close()
    return led


def capture_stencil(grid=(2, 4), domain=(32, 32), comm_mode: str = "smi"):
    """One distributed halo-exchange stencil step, captured."""
    import jax
    import numpy as np

    from ..apps import DistributedStencil

    app = DistributedStencil.create(grid, comm_mode=comm_mode)
    tiles = app.scatter(np.zeros(domain, np.float32))
    mesh = app.make_mesh()
    with _capture.capture() as led:
        f = app.jitted(mesh, n_steps=1)
        f.lower(jax.ShapeDtypeStruct(tiles.shape, tiles.dtype))
    return led


def capture_bench_collectives(size: int = 8):
    """The collective-benchmark program shape (benchmarks/ and the
    channels acceptance suite): all five collective channel kinds opened
    anonymously and driven by one whole-message transfer each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..channels import (
        open_allreduce_channel,
        open_bcast_channel,
        open_gather_channel,
        open_reduce_channel,
        open_scatter_channel,
    )
    from ..core import Communicator, make_test_mesh

    mesh = make_test_mesh((size,), ("x",))
    comm = Communicator.create("x", (size,))

    def body(v, gv, fv):
        b = open_bcast_channel(comm, root=1, port=None,
                               n_chunks=2).transfer(v[0])
        r = open_reduce_channel(comm, root=0, port=None,
                                n_chunks=2).transfer(v[0])
        gt = open_gather_channel(comm, root=0, port=None).transfer(gv[0])
        s = open_scatter_channel(comm, root=0, port=None).transfer(fv)
        a = open_allreduce_channel(comm, port=None).transfer(v[0])
        return b[None], r[None], gt[None], s[None], a[None]

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P("x"), P(None)),
                  out_specs=(P("x"),) * 5)
    with _capture.capture() as led:
        jax.jit(f).lower(
            jax.ShapeDtypeStruct((size, 4, 3), jnp.float32),
            jax.ShapeDtypeStruct((size, 2, 3), jnp.float32),
            jax.ShapeDtypeStruct((size * 2, 3), jnp.float32))
    return led


def capture_quickstart(size: int = 8, count: int = 12):
    """The quickstart example's element pipeline: a claimed p2p channel
    pushed/popped through the warm-up/drain loop (paper Listing 1), then
    a whole-message transfer + broadcast over anonymous ports."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..channels import open_bcast_channel, open_channel
    from ..core import Communicator, Topology, make_test_mesh, pvary

    mesh = make_test_mesh((size,), ("x",))
    comm = Communicator.create("x", (size,), topology=Topology.bus(size))
    src, dst = 0, 3
    hops = comm.route_table.n_hops(src, dst)

    def spmd(dummy):
        with open_channel(comm, count=count, src=src, dst=dst, port=0,
                          elem_shape=(), dtype=jnp.float32) as chan:
            acc = pvary(jnp.zeros((count,), jnp.float32), comm)

            # capture sees the traced loop body once — one push, one pop
            # in the ledger — which is exactly the per-iteration pattern
            # the credit-window walk checks (DESIGN.md §14)
            def body(i, carry):
                chan, acc = carry
                chan = chan.push(jnp.sin(i.astype(jnp.float32)))
                chan, val, valid = chan.pop()
                slot = jnp.maximum(i - (hops - 1), 0)
                acc = jnp.where(valid, acc.at[slot].set(val), acc)
                return chan, acc

            chan, acc = jax.lax.fori_loop(0, count + hops - 1, body,
                                          (chan, acc))
        y = open_channel(comm, src=src, dst=dst, port=None,
                         n_chunks=4).transfer(acc)
        y = open_bcast_channel(comm, root=dst, port=None,
                               n_chunks=2).transfer(y)
        return y[None] + 0 * dummy[:, :1]

    f = jax.shard_map(spmd, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    with _capture.capture() as led:
        jax.jit(f).lower(jax.ShapeDtypeStruct((size, 1), jnp.float32))
    return led


#: name -> zero-argument capture entry; the CLI/CI sweep
PROGRAMS = {
    "launch.train": capture_train,
    "launch.serve": capture_serve,
    "launch.stencil": capture_stencil,
    "bench.collectives": capture_bench_collectives,
    "examples.quickstart": capture_quickstart,
}


def run_programs(names=None) -> tuple[list, bool]:
    """Capture + verify each named program.  ``(rows, all_ok)``: a row
    carries the op counts, the real-step counter (must be 0) and the
    diagnostics (must be empty)."""
    rows = []
    ok = True
    for name in names or sorted(PROGRAMS):
        led = PROGRAMS[name]()
        diags = verify_ledger(led, name=name)
        clean = not diags and led.real_steps == 0
        ok = ok and clean
        rows.append({
            "program": name,
            "ops": led.counts(),
            "size": led.size,
            "real_steps": led.real_steps,
            "transport_steps": led.transport_steps,
            "ok": clean,
            "diagnostics": [d.to_dict() for d in diags],
        })
    return rows, ok
