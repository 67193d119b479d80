"""jit'd public wrapper: layout handling, padding, dispatch."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import pad_to, resolve_use_pallas
from .kernel import flash_attention_pallas
from .ref import attention_ref, attention_chunked_ref


@partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "scale", "block_q", "block_k",
        "use_pallas", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Multi-head attention with GQA; (B, S, H, D) layouts throughout.

    The Pallas kernel is forward-only; its gradient is the reference
    attention's (recomputed in XLA from the saved q, k, v)."""
    if not resolve_use_pallas(use_pallas) and not interpret:
        return _reference(q, k, v, causal, window, scale)
    return _kernel(q, k, v, causal, window, scale, block_q, block_k, interpret)


def _reference(q, k, v, causal, window, scale):
    if q.shape[1] * k.shape[1] > 2048 * 2048:
        return attention_chunked_ref(
            q, k, v, scale=scale, causal=causal, window=window
        )
    return attention_ref(q, k, v, scale=scale, causal=causal, window=window)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _kernel(q, k, v, causal, window, scale, block_q, block_k, interpret):
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    scale = scale if scale is not None else D ** -0.5

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    qf, _ = pad_to(qf, block_q, 1)
    kf, _ = pad_to(kf, block_k, 1)
    vf, _ = pad_to(vf, block_k, 1)

    out = flash_attention_pallas(
        qf, kf, vf,
        n_q_heads=H, n_kv_heads=Hkv, scale=scale,
        causal=causal, window=window, skv_actual=Skv,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    out = out[:, :Sq].reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return out


def _kernel_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out = _kernel(q, k, v, causal, window, scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _kernel_bwd(causal, window, scale, block_q, block_k, interpret, res, g):
    _, vjp = jax.vjp(
        lambda q, k, v: _reference(q, k, v, causal, window, scale), *res)
    return vjp(g)


_kernel.defvjp(_kernel_fwd, _kernel_bwd)
