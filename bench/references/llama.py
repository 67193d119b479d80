"""A plain float32 forward pass of a Llama-style decoder (Yi's architecture).

Per layer: RMSNorm, grouped-query attention with rotate-half RoPE and a
causal mask, residual; RMSNorm, SwiGLU MLP, residual; then a final RMSNorm
and the LM head over the configuration's ``vocab_size`` columns.  Weights
are read by name from the tree the benchmark made (``embed`` (V, D),
``head`` (D, V), ``final_norm``, and per layer ``norm1``,
``attn/{wq,wk,wv,wo}``, ``norm2``, ``mlp/{w_gate,w_up,w_down}`` stacked on a
leading layer axis).  Matrix products run at "highest" precision, so a TPU
keeps them in float32.

``quantize`` gives the control: every matrix product takes its operands
rounded to float8 e4m3 with one scale per tensor (weights) or per row
(activations), accumulated in float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``.  The
    gradient passes straight through the rounding, as in float8 training,
    where the backward pass keeps its own wider format."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x), axis=axis, keepdims=True)) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, w, quantize: bool):
    if quantize:
        a, w = _q8(a, -1), _q8(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, d); position s rotates pair (i, i + d/2) by s * theta^(-2i/d)."""
    S, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """q (S, H, d), k and v (S, Hkv, d); causal, each group of H / Hkv query
    heads reads one KV head."""
    S, H, d = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)


def layer(p, x, cfg: dict, quantize: bool = False):
    """One decoder layer on x (S, D); ``p`` holds this layer's weights."""
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = x.shape[0]
    h = _rms(x, p["norm1"], eps)
    a = p["attn"]
    q = _rope(_mm(h, a["wq"][:, :H * d], quantize).reshape(S, H, d), theta)
    k = _rope(_mm(h, a["wk"], quantize).reshape(S, Hkv, d), theta)
    v = _mm(h, a["wv"], quantize).reshape(S, Hkv, d)
    o = _attention(q, k, v).reshape(S, H * d)
    x = x + _mm(o, a["wo"][:H * d], quantize)
    h = _rms(x, p["norm2"], eps)
    m = p["mlp"]
    u = jax.nn.silu(_mm(h, m["w_gate"], quantize)) * _mm(h, m["w_up"], quantize)
    return x + _mm(u, m["w_down"], quantize)


def layers_of(params) -> list:
    """Per-layer weight trees, in order, from the stacked layout."""
    stacked = params["stack"]["periods"][0]
    n = stacked["norm1"].shape[0]
    return [jax.tree.map(lambda w: w[i], stacked) for i in range(n)]


CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "rms_norm_eps", "rope_theta", "vocab_size")


def head(params, x, cfg: dict, quantize: bool = False):
    """Final norm and LM head: (S, D) -> (S, vocab_size) logits.  Columns of
    a head wider than the vocabulary (a layout's padding) are no tokens and
    take no part."""
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    return _mm(x, params["head"][:, :cfg["vocab_size"]], quantize)


@partial(jax.jit, static_argnames=("cfg_items", "quantize"))
def _logits(params, tokens, cfg_items, quantize):
    cfg = dict(cfg_items)
    x = params["embed"][tokens]
    for p in layers_of(params):
        x = layer(p, x, cfg, quantize)
    return head(params, x, cfg, quantize)


def logits(params, tokens, cfg: dict, quantize: bool = False):
    """(S,) token ids -> (S, vocab_size) float32 logits."""
    items = tuple((k, cfg[k]) for k in CFG_KEYS)
    return _logits(params, jnp.asarray(tokens, jnp.int32), items, quantize)
