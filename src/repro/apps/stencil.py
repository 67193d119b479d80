"""Distributed 2D heat-diffusion stencil with compute/communication overlap.

The paper's flagship application (§5.4.2): a 4-point stencil over a domain
sharded on a (RX, RY) rank grid, halo slabs streamed through SMI channels
every timestep.  Two step schedules, numerically identical by construction:

* :meth:`DistributedStencil.step_reference` — the non-overlapped baseline:
  the halo exchange completes, then the full sweep runs on the padded tile.
* :meth:`DistributedStencil.step_overlapped` — the pipelined schedule: the
  four neighbour permutes launch first, the *interior* update (which reads
  no halo values) runs while the slabs are in flight — on TPU through the
  Pallas row-streaming kernel (``kernels/stencil``) — and only the
  boundary ring waits for :meth:`HaloExchange.finish`.  XLA sees the
  ppermute starts before the interior compute, so the ICI transfer hides
  behind the VPU sweep — the paper's "communication during computation",
  at application scope.

Bit-exactness: every output point is the same ``0.25 * (n + s + w + e)``
f32 expression in both schedules (the interior from resident values, the
ring from the padded tile), so overlapped == reference to the bit on every
transport backend — including the int8 compressed wire, where both
schedules quantise identical slabs (tests/test_apps.py).  Distributed ==
single-rank holds exactly on exact wires and within the codec error bound
on ``smi:compressed``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.collectives import _schedule_loop
from ..core.comm import Communicator
from ..core.streaming import make_test_mesh
from ..kernels.stencil import stencil_interior, stencil_ref
from .halo import HaloExchange


def _sweep(padded):
    """One 4-point sweep of a halo-padded tile: (M, N) -> (M-2, N-2).

    The single numeric expression both step schedules are built from —
    identical operand order everywhere, which is what makes the
    overlapped/reference diff exact."""
    q = padded.astype(jnp.float32)
    out = 0.25 * (q[:-2, 1:-1] + q[2:, 1:-1] + q[1:-1, :-2] + q[1:-1, 2:])
    return out.astype(padded.dtype)


@dataclass(frozen=True)
class DistributedStencil:
    """A sharded heat-diffusion run over ``grid`` = (RX, RY) ranks.

    ``use_pallas``/``interpret`` select the interior-update kernel exactly
    as ``kernels/stencil/ops.py`` does (None = TPU auto); ``transport`` /
    ``plan`` configure the halo schedule (see :class:`HaloExchange`).
    """

    comm: Communicator
    grid: tuple[int, int]
    transport: object = None
    plan: object = None
    use_pallas: bool | None = None
    interpret: bool = False

    @staticmethod
    def create(grid, *, axis_names=None, comm=None, comm_mode=None,
               transport=None, plan=None, use_pallas=None, interpret=False):
        """Build the app over a fresh communicator (row-major torus over
        ``axis_names``) unless one is passed.  ``comm_mode`` accepts the
        launch-layer strings (``"smi:compressed"`` etc.), mapped onto the
        halo channel's spec through
        :func:`repro.channels.default_channel_spec`."""
        RX, RY = grid
        if comm is None:
            if axis_names is None:
                axis_names = ("gx", "gy") if RX > 1 and RY > 1 else ("gx",)
            sizes = grid if len(axis_names) == 2 else (RX * RY,)
            comm = Communicator.create(axis_names, sizes)
        if comm_mode is not None:
            from ..channels import default_channel_spec
            from .halo import HALO_TAG

            assert transport is None, "pass comm_mode or transport, not both"
            spec = default_channel_spec(
                comm, comm_mode, kind="exchange", port=None, tag=HALO_TAG,
            )
            transport = spec.transport
        return DistributedStencil(
            comm=comm, grid=(RX, RY), transport=transport, plan=plan,
            use_pallas=use_pallas, interpret=interpret,
        )

    @property
    def halo_schedule(self) -> HaloExchange:
        return HaloExchange(
            comm=self.comm, grid=self.grid, halo=(1, 1),
            transport=self.transport, plan=self.plan,
        )

    def make_mesh(self):
        """Host-device mesh matching the communicator's axes."""
        return make_test_mesh(self.comm.axis_sizes, self.comm.axis_names)

    # -- one timestep ------------------------------------------------------

    def step_reference(self, x, transport=None):
        """Non-overlapped: exchange completes, then the full padded sweep."""
        padded = self.halo_schedule.exchange(x, transport)
        return _sweep(padded)

    def step_overlapped(self, x, transport=None):
        """Pipelined: interior update runs while the halo slabs fly.

        The interior (rows/cols ``1..-2``) reads no halo values, so it is
        traced between :meth:`HaloExchange.start` and ``finish`` — the
        overlap window; only the one-point boundary ring consumes the
        received slabs.  Every point is the same f32 expression as
        :meth:`step_reference` computes, so the two schedules agree bit
        for bit on every backend.  The four regions carry the named
        scopes ``halo.start``, ``stencil.interior``, ``halo.finish`` and
        ``stencil.assemble`` in the compiled program's op metadata.
        """
        he = self.halo_schedule
        with jax.named_scope("halo.start"):
            inflight = he.start(x, transport)
        with jax.named_scope("stencil.interior"):
            inner = stencil_interior(
                x, use_pallas=self.use_pallas, interpret=self.interpret
            )
        with jax.named_scope("halo.finish"):
            padded = he.finish(x, inflight)
        with jax.named_scope("stencil.assemble"):
            out = jnp.zeros_like(x)
            out = out.at[1:-1, 1:-1].set(inner)
            out = out.at[0, :].set(_sweep(padded[:3, :])[0])
            out = out.at[-1, :].set(_sweep(padded[-3:, :])[0])
            out = out.at[:, 0].set(_sweep(padded[:, :3])[:, 0])
            out = out.at[:, -1].set(_sweep(padded[:, -3:])[:, 0])
        return out

    # -- multi-step runs ---------------------------------------------------

    def run(self, x, n_steps: int, *, overlapped: bool = True,
            transport=None):
        """``n_steps`` timesteps of the local tile ``x`` (inside shard_map).

        Rolled (fori_loop) on trace-time backends with the per-iteration
        stats delta scaled to the full step count; unrolled when the
        backend threads runtime counters (the packet router) — the same
        dispatch the streamed collectives use.
        """
        t = self.halo_schedule.resolve_transport(x, transport)
        step = self.step_overlapped if overlapped else self.step_reference

        def body(_, v):
            return step(v, transport=t)

        return _schedule_loop(t, n_steps, body, x)

    def jitted(self, mesh=None, *, n_steps: int = 1, overlapped: bool = True,
               transport=None):
        """jit(shard_map) callable: (n, nx, ny) stacked tiles -> same;
        its program is named ``stencil_dispatch``."""
        mesh = mesh or self.make_mesh()
        names = self.comm.axis_names
        spec = P(names[0]) if len(names) == 1 else P(names)

        def stencil_dispatch(tiles):
            return self.run(
                tiles[0], n_steps, overlapped=overlapped, transport=transport
            )[None]

        return jax.jit(
            jax.shard_map(stencil_dispatch, mesh=mesh, in_specs=spec,
                          out_specs=spec)
        )

    # -- host-side domain plumbing ----------------------------------------

    def scatter(self, world: np.ndarray) -> np.ndarray:
        """(X, Y) domain -> (n_ranks, nx, ny) row-major tile stack."""
        RX, RY = self.grid
        X, Y = world.shape
        assert X % RX == 0 and Y % RY == 0, (
            f"domain {world.shape} not divisible by grid {self.grid}"
        )
        nx, ny = X // RX, Y // RY
        tiles = np.zeros((RX * RY, nx, ny), world.dtype)
        for rx in range(RX):
            for ry in range(RY):
                tiles[rx * RY + ry] = world[rx * nx:(rx + 1) * nx,
                                            ry * ny:(ry + 1) * ny]
        return tiles

    def gather(self, tiles: np.ndarray) -> np.ndarray:
        """(n_ranks, nx, ny) tile stack -> reassembled (X, Y) domain."""
        RX, RY = self.grid
        tiles = np.asarray(tiles)
        _, nx, ny = tiles.shape
        world = np.zeros((RX * nx, RY * ny), tiles.dtype)
        for rx in range(RX):
            for ry in range(RY):
                world[rx * nx:(rx + 1) * nx, ry * ny:(ry + 1) * ny] = \
                    tiles[rx * RY + ry]
        return world

    @staticmethod
    def single_rank_reference(world, n_steps: int):
        """The undistributed oracle: ``n_steps`` zero-boundary sweeps."""
        out = jnp.asarray(world)
        for _ in range(n_steps):
            out = stencil_ref(out)
        return np.asarray(out)

    # -- costing -----------------------------------------------------------

    def predicted_step_time(self, tile_shape, dtype="float32", model=None,
                            *, overlapped: bool = True,
                            compute_seconds: float | None = None,
                            wire: str = "raw") -> float:
        """LinkModel prediction of one timestep: the halo-exchange time
        combined with ``compute_seconds`` through the overlap window
        (max on the pipelined schedule, sum on the reference)."""
        from ..netsim.model import LinkModel

        model = model or LinkModel.default_v5e()
        comm_s = self.halo_schedule.predicted_time(
            tile_shape, dtype, model=model, wire=wire
        )
        if compute_seconds is None:
            return comm_s
        if overlapped:
            return model.overlapped_step_time(compute_seconds, comm_s)
        return model.serial_step_time(compute_seconds, comm_s)

    def with_transport(self, transport) -> "DistributedStencil":
        return replace(self, transport=transport)
