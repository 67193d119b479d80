"""HaloExchange: the distributed-application communication schedule.

The paper's stencil benchmark (§5.4.2, Fig. 14) decomposes a 2D domain over
a rank grid and streams boundary slabs to the four neighbours each
timestep.  :class:`HaloExchange` packages that schedule as an object the
``repro/apps`` workloads share:

* **backend-agnostic** — the slabs move through whichever transport the
  communicator (or an explicit ``transport=`` / ``comm_mode="smi:<b>"``)
  selects: static ppermutes, the packet router, the fused path, or int8
  compressed links;
* **split for overlap** — :meth:`start` launches the neighbour permutes
  and :meth:`finish` assembles the padded tile, so an application can run
  its interior compute between the two (``core/overlap.py``'s
  start/finish pair);
* **costed** — :meth:`predicted_stats` is the netsim-exact (steps, bytes)
  the backend will tally (asserted against ``stats.by_tag["halo"]``), and
  :meth:`predicted_time` is the :class:`~repro.netsim.model.LinkModel`
  step-time prediction the benchmarks print;
* **tunable** — ``plan="auto"`` asks the communicator's netsim tuning
  table which backend should move a slab of this size on this topology
  (``Communicator.plan("halo", nbytes)``; always a raw wire — lossy halos
  are an explicit user choice, never a tuned one).

The schedule's communication configuration rides in a
:class:`~repro.channels.ChannelSpec` of kind ``"exchange"`` (:attr:`spec`):
the same open-time descriptor the channel API uses everywhere else carries
the halo wire's transport backend, tuning plan, and the ``"halo"`` stats
tag — one exchange is one anonymous-port transient channel over the
neighbour links.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..channels.spec import ChannelSpec
from ..core.comm import Communicator
from ..core.overlap import (
    halo_exchange_2d_finish,
    halo_exchange_2d_start,
)

#: the tag halo wire traffic is accounted under (TransportStats.by_tag)
HALO_TAG = "halo"


@dataclass(frozen=True)
class HaloExchange:
    """The N/S/E/W halo-exchange schedule of a (RX, RY) rank grid.

    ``transport`` is a registry key / Transport instance / None (the
    communicator's default); ``plan="auto"`` defers the choice to the
    netsim tuning table per tile size.  A per-call ``transport=`` always
    wins — benchmarks pass fresh instances so traced stats stay per-run.
    """

    comm: Communicator
    grid: tuple[int, int]
    halo: tuple[int, int] = (1, 1)
    transport: object = None
    plan: object = None

    def __post_init__(self):
        RX, RY = self.grid
        assert self.comm.size == RX * RY, (
            f"grid {self.grid} needs {RX * RY} ranks; communicator has "
            f"{self.comm.size}"
        )

    # -- transport resolution ---------------------------------------------

    @property
    def spec(self) -> ChannelSpec:
        """This schedule's communication config as a transient-channel
        descriptor: an anonymous-port ``"exchange"`` channel tagged
        ``"halo"`` over the schedule's transport/plan."""
        return ChannelSpec(
            comm=self.comm, kind="exchange", port=None,
            transport=self.transport, plan=self.plan, tag=HALO_TAG,
        )

    def slab_nbytes(self, tile_shape, dtype=np.float32) -> int:
        """Bytes of the largest halo slab of a ``tile_shape`` tile (the
        message size the tuner's ``halo`` cells are keyed on)."""
        from ..netsim.schedule import halo_slab_elems

        ns, ew = halo_slab_elems(tuple(tile_shape), self.halo)
        return max(ns, ew) * np.dtype(dtype).itemsize

    def resolve_transport(self, tile=None, transport=None):
        """The Transport instance one exchange of ``tile`` uses: explicit
        argument > the spec's ``transport`` > the tuned ``halo`` plan
        (``plan="auto"``) > the communicator's default backend."""
        from ..transport.registry import resolve_transport

        spec = self.spec
        if transport is not None:
            return resolve_transport(transport, self.comm)
        if spec.transport is None and spec.plan == "auto" and tile is not None:
            p = self.comm.plan(
                "halo", self.slab_nbytes(tile.shape, tile.dtype)
            )
            return spec.replace(transport=p.transport_key).resolve()
        return spec.resolve()

    # -- the exchange ------------------------------------------------------

    def start(self, x, transport=None):
        """Launch the four neighbour permutes; returns the in-flight slabs
        (tagged ``"halo"`` in the backend's stats)."""
        t = self.resolve_transport(x, transport)
        return halo_exchange_2d_start(
            x, self.comm, grid=self.grid, halo=self.halo,
            transport=t, tag=self.spec.stats_tag,
        )

    def finish(self, x, inflight):
        """Assemble the halo-padded tile from ``x`` + the in-flight slabs."""
        return halo_exchange_2d_finish(
            x, inflight, self.comm, grid=self.grid, halo=self.halo
        )

    def exchange(self, x, transport=None):
        """Non-overlapped exchange: start and immediately finish."""
        return self.finish(x, self.start(x, transport))

    # -- costing (netsim) --------------------------------------------------

    def predicted_stats(self, tile_shape, dtype="float32",
                        transport: str = "static", **kw):
        """Exact (steps, bytes) one exchange tallies under ``transport`` —
        the numbers ``stats.by_tag["halo"]`` holds after tracing.  Extra
        kwargs (``pkt_elems`` etc.) forward to
        :func:`repro.netsim.schedule.predict_halo_stats`."""
        from ..netsim.schedule import predict_halo_stats

        return predict_halo_stats(
            self.comm, grid=self.grid, shape=tuple(tile_shape), dtype=dtype,
            halo=self.halo, transport=transport, **kw,
        )

    def predicted_time(self, tile_shape, dtype="float32", model=None,
                       wire: str = "raw") -> float:
        """LinkModel-predicted seconds of one exchange (the benchmark's
        model column)."""
        from ..netsim.schedule import predict_halo_time

        return predict_halo_time(
            self.comm, grid=self.grid, shape=tuple(tile_shape), dtype=dtype,
            halo=self.halo, model=model, wire=wire,
        )
