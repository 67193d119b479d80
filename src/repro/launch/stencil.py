"""Launch the distributed halo-exchange stencil (paper §5.4.2).

Runs ``repro.apps.DistributedStencil`` over a rank grid on host devices,
streams halos through the selected transport backend, verifies against the
single-rank sweep, and prints measured vs LinkModel-predicted step times.

    PYTHONPATH=src python -m repro.launch.stencil --case torus2x4 \\
        --comm-mode smi:compressed --steps 8
    PYTHONPATH=src python -m repro.launch.stencil --grid 2x4 \\
        --domain 512x512 --no-overlap --json out.json
    PYTHONPATH=src python -m repro.launch.stencil --trace trace.json \\
        --metrics metrics.json

``--trace`` writes a Chrome-trace / Perfetto file with one lane per rank
(measured steps), one lane per directed link (the netsim-predicted halo
flit timeline), and the channel/halo schedule events recorded while
tracing the program — the predicted-vs-measured overlay of DESIGN.md §11.
``--metrics`` snapshots the obs metrics registry (halo transport counters
per tag + the wall-vs-model drift gauge) to JSON.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time

import jax
import numpy as np

from ..configs import COMM_MODES, STENCIL_CASES


def _pair(s: str) -> tuple[int, int]:
    a, _, b = s.partition("x")
    return int(a), int(b)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--case", default=None, choices=sorted(STENCIL_CASES),
                    help="predefined (grid, domain, steps) cell")
    ap.add_argument("--grid", default=None,
                    help="rank grid RXxRY (default: the squarest grid of "
                         "the devices present)")
    ap.add_argument("--domain", default="256x256", help="global domain XxY")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--comm-mode", default="smi",
                    help=f"one of {COMM_MODES} (smi:<backend> selects the "
                         "transport; 'smi' = static; plan=auto tunes it)")
    ap.add_argument("--plan", default=None, choices=[None, "auto"],
                    help="'auto' lets the netsim tuning table pick the "
                         "halo backend")
    ap.add_argument("--no-overlap", action="store_true",
                    help="run the non-overlapped reference schedule")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write machine-readable results to OUT")
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="write a Chrome trace (rank lanes + per-link "
                         "netsim-predicted overlay) to OUT")
    ap.add_argument("--metrics", default=None, metavar="OUT",
                    help="write an obs metrics snapshot (transport "
                         "counters + drift gauges) to OUT")
    args = ap.parse_args(argv)

    from ..apps import DistributedStencil
    from .cache import enable_compile_cache
    from .mesh import grid_for

    enable_compile_cache()
    grid = _pair(args.grid) if args.grid else grid_for(jax.device_count())
    domain, steps = _pair(args.domain), args.steps
    if args.case:
        c = STENCIL_CASES[args.case]
        grid, domain, steps = c["grid"], c["domain"], c["steps"]

    if args.plan == "auto":
        if args.comm_mode != "smi":
            ap.error("--plan auto lets the tuner pick the backend; it "
                     "cannot be combined with an explicit --comm-mode")
        comm_mode = None
    else:
        comm_mode = args.comm_mode
    app = DistributedStencil.create(
        grid, comm_mode=comm_mode, plan=args.plan
    )
    mode_label = args.comm_mode if args.plan != "auto" else "smi(auto)"
    world = np.random.RandomState(0).randn(*domain).astype(np.float32)
    tiles = app.scatter(world)
    mesh = app.make_mesh()
    overlapped = not args.no_overlap

    if args.trace:
        from ..obs import trace as obs_trace
        obs_trace.enable(capacity=1 << 18)
    # an explicit transport instance (rather than the spec's lazy resolve)
    # lets the metrics registry snapshot the traced per-tag counters;
    # plan=auto must keep resolving per tile size, so it stays lazy
    tp = app.halo_schedule.resolve_transport() if args.plan != "auto" else None
    f = app.jitted(mesh, n_steps=steps, overlapped=overlapped, transport=tp)
    got = np.asarray(jax.block_until_ready(f(tiles)))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(f(tiles))
    wall = time.perf_counter() - t0

    want = app.single_rank_reference(world, steps)
    err = float(np.max(np.abs(app.gather(got) - want)))
    # lossy only when compressed links actually move the halos (tuned
    # plans are raw-wire by construction, so plan="auto" gates exactly)
    lossy = (comm_mode or "").startswith("smi:compressed")
    ok = err == 0.0 if not lossy else err < 1e-1
    nx, ny = domain[0] // grid[0], domain[1] // grid[1]
    model_s = app.predicted_step_time(
        (nx, ny), wire="int8" if lossy else "raw"
    ) * steps

    sched = "overlapped" if overlapped else "reference"
    print(f"[stencil] grid={grid} domain={domain} steps={steps} "
          f"comm_mode={mode_label} schedule={sched}")
    print(f"[stencil] wall={wall * 1e6:.1f}us  "
          f"v5e_model_halo={model_s * 1e6:.1f}us  max|err|={err:.3g} "
          f"{'OK' if ok else 'MISMATCH'}")

    from ..obs.metrics import REGISTRY
    if tp is not None:
        REGISTRY.track("halo", tp)
    REGISTRY.drift("stencil/wall_vs_model", predicted=model_s, measured=wall)

    if args.trace:
        from ..netsim.schedule import halo_rounds, halo_slab_elems
        from ..netsim.sim import simulate
        from ..obs import trace as obs_trace
        from ..obs.export import sim_report_events, write_chrome_trace

        tracer = obs_trace.disable()
        events = list(tracer.events()) if tracer else []
        # measured rank lanes: SPMD lockstep means every rank ran the same
        # schedule — split the timed wall across steps, one lane per rank
        per_step = wall / max(steps, 1)
        for r in range(app.comm.size):
            for s in range(steps):
                events.append({
                    "ts": s * per_step, "rank": r, "kind": "run.step",
                    "tag": mode_label, "port": None,
                    "attrs": {"dur": per_step, "step": s},
                })
        # predicted overlay: replay the halo rounds through the tick
        # simulator with the move log on, one lane per directed link
        ns_e, ew_e = halo_slab_elems((nx, ny))
        reports = [
            simulate(app.comm.topology, app.comm.route_table, msgs,
                     trace=True)
            for msgs in halo_rounds(grid, ns_e * 4, ew_e * 4)
        ]
        n_ev = write_chrome_trace(args.trace, events + sim_report_events(
            app.comm.topology, reports, wire="int8" if lossy else "raw",
        ))
        print(f"[stencil] wrote {n_ev} trace events to {args.trace}")

    if args.metrics:
        with open(args.metrics, "w") as fm:
            json.dump(REGISTRY.snapshot(), fm, indent=1)
        print(f"[stencil] wrote metrics snapshot to {args.metrics}")
    if args.json:
        with open(args.json, "w") as fjs:
            json.dump({
                "grid": grid, "domain": domain, "steps": steps,
                "comm_mode": mode_label, "schedule": sched,
                "wall_us": wall * 1e6, "v5e_model_halo_us": model_s * 1e6,
                "max_err": err, "ok": bool(ok),
                "metrics": REGISTRY.snapshot(),
            }, fjs, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
