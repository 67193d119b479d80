"""Fused transport: static schedule + Pallas shift-accumulate (DESIGN.md §3.3).

The ring collectives' hot path is ``acc = shift(acc) + partial`` repeated
P-1 times.  On TPU the add runs on the VPU while the *next* ppermute's ICI
transfer is already in flight; fusing the receive-side add into one Pallas
VMEM kernel removes the extra HBM round-trip XLA would otherwise emit
between the collective-permute done and the add.  Off TPU (CPU/GPU tests)
the step falls back to ``lax.ppermute`` + ``jnp`` add — bit-identical, so
backend equivalence tests cover this path too.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..kernels.common import on_tpu, pallas_call
from .registry import register_transport
from .static import StaticTransport

# VPU-native tile: 8 sublanes x 128 lanes (f32).
_LANES = 128
_SUBLANES = 8
_BLOCK_ROWS = 512


def _accum_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


@partial(jax.jit, static_argnames=("interpret",))
def fused_accumulate(a: jax.Array, b: jax.Array, *, interpret: bool = False):
    """``a + b`` as a single VMEM-tiled Pallas kernel (any shape/dtype).

    Flattens to (rows, 128) f32-tile-aligned blocks; the padding rows are
    zeros on both sides so the result slice is exact.
    """
    from jax.experimental import pallas as pl

    assert a.shape == b.shape and a.dtype == b.dtype
    n = a.size
    tile = _SUBLANES * _LANES
    rows = max((n + _LANES - 1) // _LANES, _SUBLANES)
    rows = ((rows + _SUBLANES - 1) // _SUBLANES) * _SUBLANES
    pad = rows * _LANES - n
    af = jnp.pad(a.reshape(-1), (0, pad)).reshape(rows, _LANES)
    bf = jnp.pad(b.reshape(-1), (0, pad)).reshape(rows, _LANES)
    block = min(_BLOCK_ROWS, rows)
    # grid rows must divide evenly; fall back to one whole-array block
    if rows % block:
        block = rows
    out = pallas_call(
        _accum_kernel,
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), a.dtype),
        interpret=interpret,
    )(af, bf)
    return out.reshape(-1)[:n].reshape(a.shape)


@register_transport("fused")
@dataclass
class FusedTransport(StaticTransport):
    """Static schedules with the receive+accumulate step fused on TPU.

    ``use_pallas=None`` auto-selects (TPU: kernel, elsewhere: jnp);
    ``interpret=True`` forces the kernel through the Pallas interpreter for
    CPU validation.
    """

    use_pallas: bool | None = None
    interpret: bool = False

    def _fuse(self) -> bool:
        return on_tpu() if self.use_pallas is None else self.use_pallas

    def accumulate(self, a, b):
        """Tiled-VMEM add: every reduction-combine the collective layer
        routes through :meth:`Transport.accumulate` lands on the kernel,
        not just the shift-adjacent one."""
        if not (self._fuse() or self.interpret):
            return jax.tree.map(lambda x, y: x + y, a, b)
        return jax.tree.map(
            lambda x, y: fused_accumulate(x, y, interpret=self.interpret),
            a, b,
        )

    def shift_accumulate(self, x, addend, comm, step: int = 1):
        return self.accumulate(self.shift(x, comm, step), addend)
