"""Fig. 15 / Fig. 16: SPMD distributed stencil with SMI halo exchange.

Built on the ``repro/apps`` layer: strong scaling of a 4-point stencil over
a fixed domain on 1 / 4 / 8 ranks, a weak-scaling row, and — the paper's
headline — the *pipelined* schedule sweep: overlapped vs non-overlapped
step under every transport backend (``static`` / ``packet`` / ``fused`` /
``compressed``), asserted bit-identical to each other and to the
single-rank sweep (exact wires) before any timing is reported.

Model columns come from the shared netsim :class:`LinkModel`: the halo
exchange's predicted time and the overlap window (max vs sum of
compute/comm).  ``--validate-sim`` (benchmarks/run.py) asserts the halo
schedule's *exact* traced step/byte counters equal the netsim prediction
and gates fitted time predictions within 2x of measurement — the same
drift gate the latency/injection suites run.

Domain reduced from the paper's 4096^2 x 32 steps to CPU-friendly sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import DistributedStencil
from repro.netsim import calibrate
from repro.obs.metrics import REGISTRY

from .common import (
    HBM_BW,
    ICI_BW,
    V5E_MODEL,
    csv_row,
    make_bench_transport,
    timeit,
    wire_of,
)

OVERLAP_GRID = (2, 4)
OVERLAP_DOMAIN = (256, 256)
OVERLAP_STEPS = 2


def _strong_weak_scaling(world, domain, steps):
    """The original Fig. 15 / Fig. 16 rows, through the apps layer."""
    app1 = DistributedStencil.create((1, 1), axis_names=("gx",))
    f1 = app1.jitted(app1.make_mesh(), n_steps=steps, overlapped=False)
    t1 = timeit(f1, jnp.asarray(world[None]))
    want = app1.single_rank_reference(world, steps)
    csv_row(f"stencil_fig15,{domain[0]}x{domain[1]},ranks=1", t1 * 1e6, "")

    for grid in [(2, 2), (2, 4)]:
        n = grid[0] * grid[1]
        app = DistributedStencil.create(grid)
        tiles = jnp.asarray(app.scatter(world))
        f = app.jitted(app.make_mesh(), n_steps=steps, overlapped=True)
        t = timeit(f, tiles)
        got = app.gather(np.asarray(f(tiles)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        nx, ny = domain[0] // grid[0], domain[1] // grid[1]
        # v5e model: compute/mem per rank shrinks by n; halo comm per rank
        mem_t = domain[0] * domain[1] * 4 * 2 / n / HBM_BW
        halo_t = app.halo_schedule.predicted_time((nx, ny), model=V5E_MODEL)
        model = steps * V5E_MODEL.overlapped_step_time(mem_t, halo_t)
        csv_row(f"stencil_fig15,{domain[0]}x{domain[1]},ranks={n}", t * 1e6,
                f"v5e_model_us={model * 1e6:.1f}")

    # weak scaling (fig 16): fixed per-rank tile
    rng = np.random.RandomState(1)
    for grid in [(2, 2), (2, 4)]:
        n = grid[0] * grid[1]
        dom = (256 * grid[0], 256 * grid[1])
        wrld = rng.randn(*dom).astype(np.float32)
        app = DistributedStencil.create(grid)
        tiles = jnp.asarray(app.scatter(wrld))
        f = app.jitted(app.make_mesh(), n_steps=steps, overlapped=True)
        t = timeit(f, tiles)
        per_pt = t / (dom[0] * dom[1] * steps) * 1e9
        csv_row(f"stencil_fig16_weak,ranks={n}", t * 1e6,
                f"ns_per_point={per_pt:.3f}")


def _overlap_sweep(transports, validate_sim):
    """Overlapped vs reference schedule under every transport backend."""
    grid, domain, steps = OVERLAP_GRID, OVERLAP_DOMAIN, OVERLAP_STEPS
    nx, ny = domain[0] // grid[0], domain[1] // grid[1]
    rng = np.random.RandomState(2)
    world = rng.randn(*domain).astype(np.float32)
    app = DistributedStencil.create(grid)
    mesh = app.make_mesh()
    tiles = jnp.asarray(app.scatter(world))
    want = app.single_rank_reference(world, steps)
    records = []

    for tname in transports:
        wire = wire_of(tname)
        halo_t = app.halo_schedule.predicted_time(
            (nx, ny), model=V5E_MODEL, wire=wire
        )
        mem_t = nx * ny * 4 * 2 / HBM_BW
        results = {}
        for sched, overlapped in (("ref", False), ("ovl", True)):
            tp = make_bench_transport(tname)
            f = app.jitted(mesh, n_steps=steps, overlapped=overlapped,
                           transport=tp)
            t = timeit(f, tiles)
            results[sched] = np.asarray(f(tiles))
            window = (V5E_MODEL.overlapped_step_time(mem_t, halo_t)
                      if overlapped else
                      V5E_MODEL.serial_step_time(mem_t, halo_t))
            csv_row(
                f"stencil_overlap,{domain[0]}x{domain[1]},{tname},{sched}",
                t * 1e6, f"v5e_model_us={window * steps * 1e6:.1f}",
            )
            if sched == "ovl":
                REGISTRY.track(f"stencil/{tname}", tp)
            if validate_sim and sched == "ovl":
                # exactness gate: traced halo counters == netsim prediction
                kw = {"pkt_elems": tp.pkt_elems} if tname == "packet" else {}
                pred = app.halo_schedule.predicted_stats(
                    (nx, ny), transport=tname, **kw
                )
                got = tp.stats.tag_counts("halo")
                got = (got[0] // steps, got[1] // steps)
                REGISTRY.drift(f"stencil/{tname}/halo_bytes",
                               predicted=pred[1], measured=got[1])
                assert got == pred, (
                    f"halo stats drift[{tname}]: traced/step {got} != "
                    f"predicted {pred}"
                )
        # correctness before the numbers mean anything: the two schedules
        # are bit-identical on every backend; exact wires also match the
        # single-rank sweep to the bit
        np.testing.assert_array_equal(results["ref"], results["ovl"])
        got = app.gather(results["ovl"])
        if wire == "raw":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    # halo-exchange-only calibration records (the --validate-sim gate)
    for size in (64, 128, 256):
        capp = DistributedStencil.create(grid)
        ctiles = jnp.asarray(capp.scatter(
            rng.randn(size * grid[0], size * grid[1]).astype(np.float32)
        ))

        def fn(ts):
            he = capp.halo_schedule
            return he.exchange(ts[0])[None]

        from jax.sharding import PartitionSpec as P

        f = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P(("gx", "gy")),
            out_specs=P(("gx", "gy")),
        ))
        t = timeit(f, ctiles, iters=9 if validate_sim else 5)
        steps_p, bytes_p = capp.halo_schedule.predicted_stats((size, size))
        records.append(
            calibrate.record(steps_p, bytes_p, t, f"halo_{size}x{size}")
        )
        csv_row(f"stencil_halo_exchange,{size}x{size}", t * 1e6,
                f"v5e_model_us={capp.halo_schedule.predicted_time((size, size)) * 1e6:.2f}")
    if validate_sim:
        m, _worst = calibrate.validate(records, tol=2.0, label="stencil_halo")
        # the drift gauges recompute validate's ratios through the same
        # drift_ratio formula, so the snapshot can never disagree with the
        # gate that just passed
        REGISTRY.drift_from_records("stencil_halo", records, model=m)


def run(transports=("static", "packet", "fused", "compressed"),
        validate_sim=False):
    domain, steps = (512, 512), 8
    world = np.random.RandomState(0).randn(*domain).astype(np.float32)
    _strong_weak_scaling(world, domain, steps)
    _overlap_sweep(transports, validate_sim)


if __name__ == "__main__":
    run()
