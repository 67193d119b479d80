"""4-point stencil sweep Pallas kernel (the paper's §5.4.2 application).

Hardware adaptation: the FPGA implementation streams the domain through a
shift-register pipeline with perfect on-chip reuse.  The TPU analogue is
row-block streaming: each grid step holds a (bm × N) row slab in VMEM, the
north/south boundary rows come from the neighbouring blocks' 8-row edge
tiles via clamped index_maps (double-buffered by the pipeline), and the
east/west shifts are VREG lane rotations — the shift register becomes the
vector register file.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import pallas_call

#: rows of the north/south halo tiles: one f32 sublane tile
HALO_ROWS = 8

#: VMEM bytes one (bm × N) f32 slab may take; the kernel holds the input
#: and output slabs double-buffered plus a few slab-sized temporaries, so
#: this keeps the whole step well inside the default scoped VMEM
_SLAB_BYTES = 512 * 1024


def block_rows(M: int, N: int) -> int:
    """Rows per grid step: the largest multiple of 8 whose f32 slab fits
    ``_SLAB_BYTES`` (at most 256, and no taller than the padded domain)."""
    bm = max(HALO_ROWS, min(256, _SLAB_BYTES // (4 * N)) // HALO_ROWS * HALO_ROWS)
    return min(bm, -(-M // HALO_ROWS) * HALO_ROWS)


def _stencil_kernel(up_ref, c_ref, dn_ref, o_ref, *, bm: int, n_blocks: int):
    i = pl.program_id(0)
    c = c_ref[...].astype(jnp.float32)             # (bm, N)
    up = up_ref[...].astype(jnp.float32)           # (8, N): rows above
    dn = dn_ref[...].astype(jnp.float32)           # (8, N): rows below

    north = jnp.concatenate([up[-1:], c[:-1]], axis=0)      # x[r-1, :]
    south = jnp.concatenate([c[1:], dn[:1]], axis=0)        # x[r+1, :]
    row = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
    north = jnp.where(jnp.logical_and(i == 0, row == 0), 0.0, north)
    south = jnp.where(
        jnp.logical_and(i == n_blocks - 1, row == bm - 1), 0.0, south
    )

    west = jnp.pad(c[:, :-1], ((0, 0), (1, 0)))             # x[:, c-1]
    east = jnp.pad(c[:, 1:], ((0, 0), (0, 1)))              # x[:, c+1]

    o_ref[...] = (0.25 * (north + south + west + east)).astype(o_ref.dtype)


def stencil_pallas(
    x: jax.Array,  # (M, N)
    *,
    block_m: int,
    interpret: bool = False,
) -> jax.Array:
    M, N = x.shape
    assert M % block_m == 0 and block_m % HALO_ROWS == 0
    nb = M // block_m
    hb = block_m // HALO_ROWS          # halo tiles per row block
    n_halo = M // HALO_ROWS
    kern = partial(_stencil_kernel, bm=block_m, n_blocks=nb)
    return pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((HALO_ROWS, N),
                         lambda i: (jnp.maximum(i * hb - 1, 0), 0)),
            pl.BlockSpec((block_m, N), lambda i: (i, 0)),
            pl.BlockSpec((HALO_ROWS, N),
                         lambda i: (jnp.minimum((i + 1) * hb, n_halo - 1), 0)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, x, x)
