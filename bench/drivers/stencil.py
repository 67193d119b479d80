"""Driver for the halo stencil: ``DistributedStencil.jitted`` over a rank grid.

Traffic keys: ``grid`` (RX, RY), ``domain`` (X, Y), ``checked_dispatches``
(how many of the window's dispatches the reference recomputes: the first,
and the others drawn from the seed) and ``trace_seconds``.  Each dispatch advances the whole domain by the
configuration's ``steps_per_dispatch`` timesteps, feeding its output to the
next; at most two dispatches are in flight.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds
from bench.harness import Check, Measured
from bench.references import stencil as ref

#: the widest gap between a cell of the program's output and the f32
#: reference's, over the checked dispatches: sound runs read 0, the
#: reference in bfloat16 reads 8.8e-5 or more (PERF.md); room for f32
#: rounding of the four adds in another order, far below the control
MAX_ABS_ERR = 1e-5


class Driver:
    def __init__(self, cell, *, seed: int, devices, seconds: float):
        t, c = cell.traffic, cell.config
        self.cell, self.seed, self.devices = cell, seed, devices
        self.grid = tuple(t["grid"])
        self.domain = tuple(t["domain"])
        self.steps = int(c["steps_per_dispatch"])
        self.n_checked = int(t["checked_dispatches"])
        self.comm_mode = c["comm_mode"]

    def build(self):
        """The compiled dispatch under test and the layout of its tiles."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.apps import DistributedStencil

        app = DistributedStencil.create(self.grid, comm_mode=self.comm_mode)
        mesh = app.make_mesh()
        names = app.comm.axis_names
        spec = P(names[0]) if len(names) == 1 else P(names)
        f = app.jitted(mesh, n_steps=self.steps, overlapped=True,
                       transport=app.halo_schedule.resolve_transport())
        return f, NamedSharding(mesh, spec)

    def setup(self) -> None:
        self.f, sharding = self.build()
        RX, RY = self.grid
        X, Y = self.domain
        shape = (RX * RY, X // RX, Y // RY)
        make = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                       out_shardings=sharding)
        x = make(seeds.jax_key(self.seed, "stencil.domain"))
        self.x = self.f(x).block_until_ready()   # compiles; the window's shape

    def measure(self, win) -> Measured:
        rng = seeds.numpy_rng(self.seed, "stencil.checked")
        kept: list[tuple] = []   # reservoir of (input, output) pairs
        x, prev, n = self.x, None, 0

        def sync():
            x.block_until_ready()

        win.start()
        while win.open:
            win.tick(sync)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = self.f(x)
            if len(kept) < self.n_checked:
                kept.append((x, y))
            else:
                # the window's first dispatch stays: its input still holds
                # the seed's rough field, where a wrong halo shows most
                j = int(rng.integers(1, n + 1))
                if j < self.n_checked:
                    kept[j] = (x, y)
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    prev.block_until_ready()
            prev, x, n = y, y, n + 1
        x.block_until_ready()
        win.stop()
        self.x, self.kept = x, kept
        X, Y = self.domain
        cells = n * self.steps * X * Y
        return Measured(
            metrics={"stencil_gcells_per_s": cells / win.elapsed / 1e9},
            attempted=n, failed=0,
            stats={"steps_per_dispatch": self.steps, "grid": self.grid,
                   "domain": self.domain, "traced": win.traced})

    def release(self) -> None:
        """Free the program's state; the checked pairs stay."""
        del self.x, self.f

    def check(self) -> list[Check]:
        worst = 0.0
        dev = self.devices[0]
        for x_in, x_out in self.kept:
            world = ref.assemble(jax.device_put(x_in, dev), self.grid)
            got = ref.assemble(jax.device_put(x_out, dev), self.grid)
            want = ref.run(world, self.steps)
            worst = max(worst, float(jnp.max(jnp.abs(got - want))))
        return [Check("max_abs_err", worst, MAX_ABS_ERR)]


def control(drv) -> float:
    """The control's reading of ``max_abs_err``: the reference computed in
    bfloat16 in the program's place, on the same checked dispatches."""
    worst = 0.0
    for x_in, _ in drv.kept:
        world = ref.assemble(jax.device_put(x_in, drv.devices[0]), drv.grid)
        low = ref.run(world, drv.steps, jnp.bfloat16)
        want = ref.run(world, drv.steps)
        worst = max(worst, float(jnp.max(jnp.abs(low - want))))
    return worst
