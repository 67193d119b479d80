"""MXU-tiled matmul Pallas kernel.

The per-chunk GEMM of the SMI overlap engine (core/overlap.py): each ring
step multiplies one streamed chunk on the MXU while the next chunk rides the
ICI.  Block sizes default to (128, 128, 128) — MXU-native tiles; the K grid
dim is innermost ("arbitrary": sequential) and accumulates into an f32 VMEM
scratch so low-precision inputs keep full-precision partials.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import pallas_call


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K) @ w: (K, N) -> (M, N).  Dims must divide the block sizes
    (ops.py pads).  Grid: (M/bm, N/bn, K/bk), K innermost sequential."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, f"contraction mismatch {K} vs {K2}"
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    out_dtype = out_dtype or x.dtype
    grid = (M // block_m, N // block_n, K // block_k)
    kernel = partial(_matmul_kernel, nk=grid[2])
    return pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)
