"""Mamba2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD recurrence  h_t = exp(dt_t·A) h_{t-1} + B_t (dt_t·x_t),
                    y_t = C_t · h_t
is computed chunk-by-chunk (arXiv:2405.21060): within a chunk the output is a
masked, decay-weighted quadratic form (MXU work — "attention duality"), and
the chunk boundary state is carried through the innermost sequential grid
dimension in VMEM scratch — the same carry pattern the matmul kernel uses
for K blocks.  Numerically safe for A < 0, dt > 0 (all exponents ≤ 0).

Grid: (B*H, n_chunks); one (L × Dh) x-tile and (L × Dst) B/C tiles per step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import pallas_call


def _ssd_kernel(
    x_ref,      # (1, L, Dh)
    dt_ref,     # (1, 1, L)  dt as a row
    b_ref,      # (1, L, Dst)
    c_ref,      # (1, L, Dst)
    a_ref,      # (1, 1, 1)  A (negative) for this head
    y_ref,      # (1, L, Dh)
    state_ref,  # VMEM (Dst, Dh) carry
    *, L: int,
):
    c_i = pl.program_id(1)

    @pl.when(c_i == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (L, Dh)
    B = b_ref[0].astype(jnp.float32)          # (L, Dst)
    C = c_ref[0].astype(jnp.float32)          # (L, Dst)
    A = a_ref[0].astype(jnp.float32)          # (1, 1)
    dt_row = dt_ref[0].astype(jnp.float32)    # (1, L)

    # dt as a column, and inclusive prefix sums of the decay logs as a
    # column and as a row, all from masked reductions (Mosaic has no
    # cumsum, and a (1, L) -> (L, 1) transpose is not worth a relayout)
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = ii >= jj
    dt = jnp.sum(jnp.where(ii == jj, dt_row, 0.0), axis=1, keepdims=True)
    a_col = dt * A                            # (L, 1) decay logs (<= 0)
    a_row = dt_row * A                        # (1, L)
    cum = jnp.sum(jnp.where(causal, a_row, 0.0), axis=1, keepdims=True)  # (L,1)
    cum_row = jnp.sum(jnp.where(ii <= jj, a_col, 0.0), axis=0,
                      keepdims=True)                                 # (1,L)
    xd = x * dt                               # dt-weighted input

    # intra-chunk: y1[i] = sum_{j<=i} (C_i·B_j) exp(cum_i - cum_j) xd_j
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)
    decay = jnp.exp(cum - cum_row)            # (L, L)
    scores = jnp.where(causal, G * decay, 0.0)
    y1 = jax.lax.dot_general(scores, xd, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, Dh)

    # inter-chunk: y2[i] = exp(cum_i) C_i · h_in
    h_in = state_ref[...]                      # (Dst, Dh)
    y2 = jnp.exp(cum) * jax.lax.dot_general(
        C, h_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # (L, Dh)

    y_ref[0] = (y1 + y2).astype(y_ref.dtype)

    # state out: h = exp(cum_L) h_in + sum_j exp(cum_L - cum_j) B_j ⊗ xd_j
    last = jnp.sum(a_row, axis=1, keepdims=True)   # (1, 1) == cum_L
    w = jnp.exp(last - cum)                    # (L, 1)
    state_ref[...] = jnp.exp(last) * h_in + jax.lax.dot_general(
        B * w, xd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def ssd_pallas(
    x: jax.Array,    # (BH, S, Dh)
    dt: jax.Array,   # (BH, S)
    B: jax.Array,    # (BH, S, Dst)
    C: jax.Array,    # (BH, S, Dst)
    A: jax.Array,    # (BH, 1)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, S, Dh = x.shape
    Dst = B.shape[-1]
    assert S % chunk == 0
    n_chunks = S // chunk
    kern = partial(_ssd_kernel, L=chunk)
    # every block's last two dims are (8, 128)-divisible or whole: dt
    # rides as a (1, S) row, A as a (1, 1) tile
    return pallas_call(
        kern,
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, Dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, Dst), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, Dst), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, Dh), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((Dst, Dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt[:, None, :], B, C, A[:, :, None])
