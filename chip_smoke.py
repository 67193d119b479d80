"""Smoke run of the main paths on a TPU, through the entry points users call.

    python chip_smoke.py             # one chip: serve, train, stencil, kernels
    python chip_smoke.py --chips 4   # four chips: TP decode and 2x2 stencil

One process drives every phase.  Each phase prints one line (what ran, what
was cut to fit, its error against the reference, wall seconds including
compilation) and raises on failure, so the JSON line that ends a passing
run is never printed by a failing one.  Weights and data come from
``--seed``.  The script refuses to run anywhere but a TPU, and needs the
repository's ``src`` beside it.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

#: yi-6b (arXiv:2403.04652) at its published widths, cut only in depth
SERVE_LAYERS = 12  # 32 layers of f32 masters + a bf16 copy need ~36 GB;
#                    memory_analysis puts 12 at 14.5 GB of v5e's 15.75 GB
TRAIN_LAYERS, TRAIN_SEQ, TRAIN_BATCH = 1, 2048, 4  # AdamW: ~16 B/param
TP_LAYERS = 8  # f32 one-chip copy (7.6 GB) + its TP shard on device 0
SSD_TOL = 2e-4  # ssd kernel at "highest" vs its reference on the host; the
#                 chip read 1.53e-4 at an output of 53 (PERF.md)


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def has_kernel(compiled) -> bool:
    """A Mosaic kernel, not an interpreter or a reference, is in the
    compiled program."""
    return "tpu_custom_call" in compiled.as_text()


def yi6b(n_layers: int, **kw):
    from repro.configs import get_arch

    return get_arch("yi-6b").scaled(n_layers=n_layers, **kw)


def reduced(cfg) -> str:
    return f"n_layers:32->{cfg.n_layers}"


def requests(cfg, n: int, max_new: int, seed: int):
    from repro.serving import Request

    rng = np.random.RandomState(seed)
    return [Request(uid=u, prompt=rng.randint(0, cfg.vocab_size,
                                              rng.randint(3, 9)).tolist(),
                    max_new=max_new) for u in range(n)]


def drain(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    done = eng.run(max_steps=1024)
    assert len(done) == len(reqs), f"{len(done)}/{len(reqs)} requests done"
    return {r.uid: r.out for r in done}


def mismatches(got: dict, want: dict) -> int:
    assert got.keys() == want.keys(), (sorted(got), sorted(want))
    return sum(a != b for u in got for a, b in zip(got[u], want[u])) + sum(
        abs(len(got[u]) - len(want[u])) for u in got)


# ---------------------------------------------------------------- one chip


def phase_serve(cfg, *, seed: int, n_requests=4, max_new=8, slots=4,
                capacity=64) -> None:
    """Continuous-batching decode; greedy tokens must equal the wave
    engine's on the same params (the oracle ContinuousEngine promises)."""
    from repro.launch.serve import init_params
    from repro.mesh.api import ParallelCtx
    from repro.serving import ContinuousEngine, ServeEngine

    t0 = time.perf_counter()
    ctx = ParallelCtx()
    params = init_params(cfg, ctx, seed)
    with ContinuousEngine(cfg, params, ctx=ctx, batch_slots=slots,
                          capacity=capacity) as eng:
        got = drain(eng, requests(cfg, n_requests, max_new, seed))
    want = drain(ServeEngine(cfg, params, ctx=ctx, batch_slots=slots,
                             capacity=capacity),
                 requests(cfg, n_requests, max_new, seed))
    bad = mismatches(got, want)
    log("serve", arch=cfg.name, dtype=cfg.dtype, reduced=reduced(cfg),
        requests=n_requests, tokens=sum(map(len, got.values())),
        mismatched_tokens_vs_wave=bad, wall_s=time.perf_counter() - t0)
    assert all(len(o) == max_new for o in got.values()), got
    assert bad == 0, f"continuous {got} != wave {want}"


def phase_train(cfg, *, seed: int, steps=3) -> None:
    """build_train + train_loop: finite losses, flash attention compiled
    as a Mosaic kernel into the step."""
    import jax

    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import TrainSettings, build_train
    from repro.launch.train import train_loop

    t0 = time.perf_counter()
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("chip_smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    settings = TrainSettings(comm_mode="smi:static")
    art = build_train(cfg, mesh, shape, settings)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in art["input_specs"].items()}
    kernel = has_kernel(art["step"].lower(art["state_shape"], batch).compile())
    _, hist = train_loop(cfg, mesh, shape, settings, steps=steps, log_every=1,
                         seed=seed)
    losses = [h["loss"] for h in hist]
    log("train", arch=cfg.name, reduced=f"{reduced(cfg)} seq={TRAIN_SEQ} "
        f"batch={TRAIN_BATCH}", steps=len(losses), losses=losses,
        flash_kernel=kernel, wall_s=time.perf_counter() - t0)
    assert len(losses) == steps and all(map(math.isfinite, losses)), losses
    assert kernel, "train step has no tpu_custom_call (flash attention)"


def phase_stencil(grid: str, domain: str, comm_mode: str, steps=8) -> None:
    """launch/stencil end to end; it compares against the single-rank
    sweep and returns 1 on any mismatch."""
    from repro.launch import stencil

    t0 = time.perf_counter()
    rc = stencil.main(["--grid", grid, "--domain", domain, "--steps",
                       str(steps), "--comm-mode", comm_mode])
    log("stencil", grid=grid, domain=domain, comm_mode=comm_mode,
        steps=steps, rc=rc, wall_s=time.perf_counter() - t0)
    assert rc == 0, f"stencil {grid} {domain} {comm_mode} mismatched"


def check_kernel(name, fn, ref, args, tol, *, precision=None,
                 ref_on_host=False, **extra):
    """``fn`` compiles to a Mosaic kernel and agrees with ``ref`` element by
    element, ``|got - want| <= tol``.  Both are traced at matmul
    ``precision`` when one is given; ``ref_on_host`` runs ``ref`` on the
    host's CPU device, whose ``exp`` is exact to f32 rounding."""
    import contextlib

    import jax

    t0 = time.perf_counter()
    ref_args = jax.device_put(args, jax.devices("cpu")[0]) if ref_on_host \
        else args
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        compiled = jax.jit(fn).lower(*args).compile()
        want = np.asarray(jax.jit(ref)(*ref_args), np.float32)
    got = np.asarray(compiled(*args), np.float32)
    err = float(np.max(np.abs(got - want)))
    log("kernel", name=name, **extra, max_err=err, tol=tol,
        tpu_custom_call=has_kernel(compiled),
        wall_s=time.perf_counter() - t0)
    assert has_kernel(compiled), f"{name}: no Mosaic kernel compiled"
    assert np.all(np.isfinite(got)) and err <= tol, f"{name}: err {err}"


def phase_kernels(seed: int) -> None:
    """Each main-path kernel once at full width against its ref.py."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from repro.kernels import (
        attention_ref,
        flash_attention,
        ssd_ref,
        ssd_scan,
        stencil_ref,
        stencil_step,
    )
    from repro.transport.fused import fused_accumulate

    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    nrm = lambda shape, dt=jnp.float32: jax.random.normal(next(ks), shape, dt)

    # yi-6b attention: 32 q / 4 kv heads, head_dim 128, seq 2048, bf16
    q = nrm((1, 2048, 32, 128), jnp.bfloat16)
    k, v = nrm((1, 2048, 4, 128), jnp.bfloat16), nrm((1, 2048, 4, 128),
                                                       jnp.bfloat16)
    check_kernel("flash_attention",
                 partial(flash_attention, causal=True, use_pallas=True),
                 partial(attention_ref, causal=True), (q, k, v), 3e-2,
                 shape="b1.s2048.h32/4.d128.bf16")

    # mamba2-2.7b SSD: 80 heads x seq 2048 x headdim 64, state 128, f32,
    # with f32 MXU passes.  The sequential reference runs on the host: the
    # chip's exp errs by ~5e-6 relative, and 2048 chained decays make the
    # reference there off by ~1e-2, a hundred times the kernel's error
    BH, S, Dh, Dst = 80, 2048, 64, 128
    x, B, C = nrm((BH, S, Dh)), nrm((BH, S, Dst)), nrm((BH, S, Dst))
    dt = jax.nn.softplus(nrm((BH, S)) - 4.0)
    A = -jnp.exp(nrm((BH, 1)))
    check_kernel("ssd", partial(ssd_scan, use_pallas=True), ssd_ref,
                 (x, dt, B, C, A), SSD_TOL, precision="highest",
                 ref_on_host=True,
                 shape="bh80.s2048.dh64.n128.f32")

    check_kernel("stencil", partial(stencil_step, use_pallas=True),
                 stencil_ref, (nrm((4096, 4096)),), 0.0, shape="4096x4096.f32")

    a, b = nrm((1 << 20,)), nrm((1 << 20,))
    check_kernel("fused_accumulate", fused_accumulate, jnp.add, (a, b), 0.0,
                 shape="1M.f32")

    check_router_tick(seed)


def check_router_tick(seed: int, ticks=12) -> None:
    """The router tick kernel against the vector datapath's tick
    (``ref.router_tick``) on one rank of a 2x4 torus: random staged FIFOs
    and random link arrivals for a dozen ticks, with every outgoing row and
    every state tensor compared.  The cross-chip router runs in the
    four-chip TP decode on ``smi:packet:pallas``."""
    from functools import partial

    import jax.numpy as jnp

    from repro.core import Topology
    from repro.core.router import RouterConfig, make_links, make_router_tables
    from repro.kernels.router import router_tick, router_tick_pallas, \
        tick_spec_of

    dims, n, r = (2, 4), 8, 5
    cfg = RouterConfig(dims=dims)
    spec = tick_spec_of(cfg, n, [lid for lid, _ in make_links(dims)])
    my_tbl = jnp.asarray(make_router_tables(Topology.torus(dims), dims))[r]
    NP, FC, NL, E = cfg.n_ports, cfg.fifo_cap, spec.n_links, cfg.pkt_elems
    rng = np.random.RandomState(seed)
    args = (rng.randn(NP, FC, E).astype(np.float32),
            rng.randint(0, n, (NP, FC)).astype(np.int32),
            rng.randint(0, FC + 1, NP).astype(np.int32),
            rng.randn(ticks, NL, E).astype(np.float32),
            rng.randint(0, n, (ticks, NL)).astype(np.int32),
            rng.randint(0, NP, (ticks, NL)).astype(np.int32),
            rng.rand(ticks, NL) < 0.7)

    def run(tick, inq_pay, inq_dst, inq_len, pay, dst, prt, val):
        z = lambda *sh_dt: jnp.zeros(*sh_dt)
        st = dict(
            inq_head=z((NP,), jnp.int32), tr_pay=z((cfg.transit_cap, E)),
            tr_dst=z((cfg.transit_cap,), jnp.int32),
            tr_port=z((cfg.transit_cap,), jnp.int32),
            tr_head=z((), jnp.int32), tr_cnt=z((), jnp.int32),
            out_pay=z((NP, cfg.out_cap, E)), out_cnt=z((NP,), jnp.int32),
            overflow=z((), jnp.int32), last_src=z((NL,), jnp.int32),
            stick=z((NL,), jnp.int32), t_done=z((), jnp.int32))
        outs = []
        for t in range(ticks):
            st, *sent = tick(spec, my_tbl, inq_pay, inq_dst, inq_len, st,
                             pay[t], dst[t], prt[t], val[t], jnp.int32(r),
                             jnp.int32(t))
            outs += sent
        return jnp.concatenate([jnp.ravel(o).astype(jnp.float32)
                                for o in outs + [st[k] for k in sorted(st)]])

    check_kernel("router_tick",
                 partial(run, partial(router_tick_pallas, interpret=False)),
                 partial(run, router_tick), args, 0.0,
                 shape=f"torus2x4.rank{r}.ports{NP}.links{NL}.E{E}",
                 ticks=ticks)


# -------------------------------------------------------------- four chips


def phase_tp_decode(cfg, comm_mode: str, *, seed: int, n_requests=4,
                    max_new=8, slots=4, capacity=64) -> None:
    """TP continuous decode over mesh (1, 4) against the one-chip engine on
    device 0, same params, f32 at highest matmul precision.  The TP weights
    are made sharded, as ``launch.serve --mesh 1,4`` makes them; the
    one-chip engine gets them gathered onto device 0."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.launch.serve import init_params
    from repro.launch.steps import build_continuous_serve
    from repro.serving import ContinuousEngine

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        mesh = make_mesh((1, 4), ("data", "model"))
        rt = build_continuous_serve(cfg, mesh, comm_mode=comm_mode,
                                    batch_slots=slots, capacity=capacity)
        tp_params = init_params(cfg, rt["ctx"], seed, rt["param_sharding"])
        head = tp_params["head"]
        params = jax.device_put(tp_params, jax.devices()[0])
        with ContinuousEngine(cfg, params, batch_slots=slots,
                              capacity=capacity) as one:
            want = drain(one, requests(cfg, n_requests, max_new, seed))
        spread = {s.device for s in head.addressable_shards}
        with ContinuousEngine(cfg, tp_params, runtime=rt) as eng:
            caches = jax.tree.leaves(eng.caches)[0]
            got = drain(eng, requests(cfg, n_requests, max_new, seed))
    bad = mismatches(got, want)
    log("tp_decode", arch=cfg.name, dtype=cfg.dtype, reduced=reduced(cfg),
        mesh="1x4", comm_mode=comm_mode, requests=n_requests,
        tokens=sum(map(len, got.values())),
        head_shard=tuple(head.addressable_shards[0].data.shape),
        mismatched_tokens_vs_one_chip=bad, wall_s=time.perf_counter() - t0)
    assert len(spread) == 4, f"head param on {spread}, not four devices"
    assert len(caches.sharding.device_set) == 4, caches.sharding
    assert bad == 0, f"TP {got} != one-chip {want}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the cross-chip paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("device", **dev)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; this script measures nothing on "
              f"{dev['platform']}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" found {len(devs)}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache

    log("compile_cache", dir=enable_compile_cache())
    if args.chips == 4:
        tp = yi6b(TP_LAYERS, dtype="float32")
        for mode in ("smi:static", "smi:packet", "smi:packet:pallas"):
            phase_tp_decode(tp, mode, seed=args.seed)
        for mode in ("smi:static", "smi:packet"):
            phase_stencil("2x2", "8192x8192", mode)
    else:
        phase_serve(yi6b(SERVE_LAYERS), seed=args.seed)
        phase_train(yi6b(TRAIN_LAYERS), seed=args.seed)
        phase_stencil("1x1", "4096x4096", "smi:static")
        phase_kernels(args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
