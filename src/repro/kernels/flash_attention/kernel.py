"""Flash-attention forward Pallas kernel (online softmax, causal/local).

TPU adaptation notes: the FPGA notion of a fully-pipelined attention datapath
becomes MXU-tiled block processing — (bq × d) query tiles resident in VMEM,
K/V streamed block-by-block through the innermost sequential grid dim with
running max/normaliser in VMEM scratch.  GQA is handled in the BlockSpec
index maps (query head -> shared KV head), so no repeated KV materialisation
ever touches HBM.  Supports causal masking and a sliding local window
(RecurrentGemma's 1:2 local-attention layers).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import pallas_call


NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, nk: int, bq: int, bk: int, scale: float,
    causal: bool, window: int | None, skv: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks that are entirely in the causal/window shadow are skipped
    # (the @pl.when guard keeps the schedule static but elides the FLOPs)
    q_first = iq * bq
    q_last = iq * bq + bq - 1
    k_first = ik * bk
    needed = True
    if causal:
        needed = k_first <= q_last
    if window is not None:
        k_last = ik * bk + bk - 1
        needed = jnp.logical_and(needed, k_last > q_first - window)

    @pl.when(needed)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # (bq, bk)
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < skv  # ignore zero-padded keys
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,      # (B*H, Sq, D)
    k: jax.Array,      # (B*Hkv, Skv, D)
    v: jax.Array,      # (B*Hkv, Skv, D)
    *,
    n_q_heads: int,
    n_kv_heads: int,
    scale: float,
    causal: bool = True,
    window: int | None = None,
    skv_actual: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    H, Hkv = n_q_heads, n_kv_heads
    g = H // Hkv
    assert Sq % block_q == 0 and Skv % block_k == 0
    grid = (BH, Sq // block_q, Skv // block_k)
    skv = skv_actual if skv_actual is not None else Skv

    def kv_idx(bh, iq, ik):
        return ((bh // H) * Hkv + (bh % H) // g, ik, 0)

    kern = partial(
        _fa_kernel, nk=grid[2], bq=block_q, bk=block_k,
        scale=scale, causal=causal, window=window, skv=skv,
    )
    return pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), kv_idx),
            pl.BlockSpec((1, block_k, D), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
