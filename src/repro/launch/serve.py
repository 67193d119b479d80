"""Serving driver: wave or continuous-batching decode.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \\
        --requests 6 --max-new 12

    # tensor-parallel continuous batching on persistent channels
    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \\
        --mesh 1,8 --comm-mode smi:static

    # predicted-vs-measured channel gate for ONE decode step + migration
    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \\
        --mesh 2,4 --comm-mode smi:static --validate-comm

``--engine wave`` runs the lock-step wave engine (single-device only —
the bit-exactness oracle); the default continuous engine admits into any
free slot and, under a model-parallel mesh, decodes over ONE persistent
channel per layer tag from the serving :class:`~repro.channels.
ChannelPool`, released at shutdown.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import COMM_MODES, get_arch, smoke
from ..mesh.api import ParallelCtx
from ..models import init_lm
from ..serving import ContinuousEngine, Request, ServeEngine
from .cache import enable_compile_cache
from .mesh import make_mesh
from .steps import build_continuous_serve


def validate_comm(cfg, mesh, dims, args) -> int:
    """Predicted-vs-measured channel traffic gate for the serving step
    (DESIGN.md §12/§13): traces one continuous decode step plus one slot
    migration (abstract lowering), captures the tagged channel ledger,
    and diffs it against :func:`repro.netsim.predict_decode_step_stats`
    per ``serve.*`` tag.  Byte-exact, like the training gate."""
    from ..netsim import predict_decode_step_stats
    from ..parallel import ledger

    if ":" not in args.comm_mode:
        print("[validate-comm] need a pinned backend (smi:<backend>); "
              "bare 'smi' lets the per-tag tuner pick schedules the "
              "predictor cannot see")
        return 2
    dp, tp = int(np.prod(dims[:-1])), dims[-1]
    rt = build_continuous_serve(cfg, mesh, comm_mode=args.comm_mode,
                                batch_slots=args.slots,
                                capacity=args.capacity)
    ctx = rt["ctx"]
    B = rt["batch_slots"]
    pshapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg, ctx))
    cshapes = jax.eval_shape(rt["init_caches"])
    tok = jax.ShapeDtypeStruct(
        (B, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B,), jnp.int32
    )
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    migrations = 1 if tp > 1 else 0
    with ledger.capture() as led:
        rt["step"].lower(pshapes, cshapes, tok, pos)
        if migrations:
            infl = jax.eval_shape(rt["migrate_start"], cshapes, slot)
            rt["migrate_start"].lower(cshapes, slot)
            rt["migrate_finish"].lower(cshapes, infl, slot)
    measured = {t: dict(e) for t, e in led.by_tag.items()}
    predicted = predict_decode_step_stats(
        cfg, (dp, tp), B, args, capacity=args.capacity,
        migrations=migrations,
    )
    if rt["pool"] is not None:
        rt["pool"].close()

    mesh_s = ",".join(str(d) for d in dims)
    print(f"[validate-comm] arch={cfg.name} mesh={mesh_s} "
          f"comm={args.comm_mode} slots={B} migrations={migrations}")
    print(f"  {'tag':<22} {'pred bytes':>12} {'meas bytes':>12} "
          f"{'pred steps':>11} {'meas steps':>11}")
    failures = 0
    for tag in sorted(set(predicted) | set(measured)):
        p = predicted.get(tag, {"steps": 0, "bytes": 0})
        m = measured.get(tag, {"steps": 0, "bytes": 0})
        ok = p == m
        failures += 0 if ok else 1
        print(f"  {tag:<22} {p['bytes']:>12} {m['bytes']:>12} "
              f"{p['steps']:>11} {m['steps']:>11}  {'ok' if ok else 'FAIL'}")
    if failures:
        print(f"[validate-comm] FAIL: {failures} tag(s) diverge")
        return 1
    print(f"[validate-comm] ok: {len(measured)} tags byte-exact "
          f"({sum(e['bytes'] for e in measured.values())} bytes/step)")
    return 0


def init_params(cfg, ctx, seed: int = 0, sharding=None):
    """Random weights from ``seed``, made in one compiled program and laid
    out by ``sharding`` (the runtime's ``param_sharding``) as they are made.
    Run op by op, the initializer's temporaries outgrow HBM beside the
    weights; made whole on one device and then placed, a model that only
    fits sharded would not fit at all."""
    return jax.jit(lambda k: init_lm(k, cfg, ctx),
                   out_shardings=sharding)(jax.random.PRNGKey(seed))


def _submit_all(eng, cfg, n_requests, max_new, seed=0):
    rng = np.random.RandomState(seed)
    for uid in range(n_requests):
        plen = int(rng.randint(3, 9))
        if cfg.n_codebooks > 1:
            prompt = rng.randint(
                0, cfg.vocab_size, (plen, cfg.n_codebooks)
            ).tolist()
        else:
            prompt = rng.randint(0, cfg.vocab_size, (plen,)).tolist()
        eng.submit(Request(uid=uid, prompt=prompt, max_new=max_new))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "wave"])
    ap.add_argument("--mesh", default="1,1", help="data,model grid")
    ap.add_argument("--comm-mode", default="smi", choices=list(COMM_MODES))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--validate-comm", action="store_true",
                    help="trace one serve step + migration and gate the "
                         "serve.* channel ledger against netsim, byte-exact")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    dims = tuple(int(x) for x in args.mesh.split(","))
    parallel = int(np.prod(dims)) > 1

    if args.validate_comm:
        mesh = make_mesh(dims, ("data", "model"))
        return validate_comm(cfg, mesh, dims, args)

    if parallel:
        if args.engine == "wave":
            print("[serve] the wave engine is single-device only; use "
                  "--engine continuous for a parallel mesh")
            return 2
        mesh = make_mesh(dims, ("data", "model"))
        rt = build_continuous_serve(cfg, mesh, comm_mode=args.comm_mode,
                                    batch_slots=args.slots,
                                    capacity=args.capacity)
        params = init_params(cfg, rt["ctx"], sharding=rt["param_sharding"])
        eng = ContinuousEngine(cfg, params, runtime=rt)
        if rt["pool"] is not None:
            print(f"[serve] persistent channels: "
                  f"{sorted(rt['pool'].ports().items())}")
    else:
        ctx = ParallelCtx()
        params = init_params(cfg, ctx)
        cls = ServeEngine if args.engine == "wave" else ContinuousEngine
        eng = cls(cfg, params, ctx=ctx, batch_slots=args.slots,
                  capacity=args.capacity)

    _submit_all(eng, cfg, args.requests, args.max_new)
    t0 = time.time()
    done = eng.run(max_steps=1024)
    dt = time.time() - t0
    toks = sum(len(r.out) for r in done)
    print(f"[serve] engine={args.engine} completed {len(done)}/"
          f"{args.requests} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s)")
    for r in done:
        print(f"  req {r.uid}: {r.out[:8]}{'...' if len(r.out) > 8 else ''}")
    if isinstance(eng, ContinuousEngine):
        eng.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
