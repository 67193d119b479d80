"""A plain float32 training step: next-token cross entropy, its gradient,
clipping by global norm, and AdamW under a warmup-cosine learning rate.

The loss is the mean over every token of the batch of ``logsumexp(logits)
- logits[label]``, the softmax taken over the configuration's
``vocab_size`` columns of the head.  The gradient is taken one row of the batch at a time, each layer
recomputed in the backward pass, so that the step fits in one chip's
memory.  The recipe (``optimizer`` in the traffic file) is:

    lr(t) = base_lr * t / warmup                       while t < warmup
          = base_lr * (r + (1 - r) * (1 + cos(pi * p)) / 2)    after, with
            p = (t - warmup) / (total - warmup) clipped to [0, 1], r = min ratio
    g <- g * min(1, clip / |g|)                         (global norm)
    m <- b1 m + (1 - b1) g ;  v <- b2 v + (1 - b2) g^2  (t counts from 1)
    p <- p - lr(t - 1) * (m / (1 - b1^t) / (sqrt(v / (1 - b2^t)) + eps) + wd p)
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.references import llama

def _row_loss(params, tokens, labels, cfg_items, quantize):
    cfg = dict(cfg_items)
    x = params["embed"][tokens]
    for p in llama.layers_of(params):
        x = jax.checkpoint(partial(llama.layer, cfg=cfg, quantize=quantize))(p, x)
    lg = llama.head(params, x, cfg, quantize)
    ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, labels[:, None], 1)[:, 0]
    return jnp.sum(ce)


@partial(jax.jit, static_argnames=("cfg_items", "quantize"))
def _batch_grad(params, tokens, labels, cfg_items, quantize):
    """Summed loss and gradient over the batch's rows, one row at a time."""
    grad = jax.value_and_grad(_row_loss)

    def row(carry, xs):
        total, acc = carry
        loss, g = grad(params, xs[0], xs[1], cfg_items, quantize)
        return (total + loss, jax.tree.map(jnp.add, acc, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (total, acc), _ = jax.lax.scan(row, zero, (tokens, labels))
    return total, acc


def loss_and_grad(params, tokens, labels, cfg: dict, quantize: bool = False):
    """Mean cross entropy over the (B, S) batch and its gradient."""
    items = tuple((k, cfg[k]) for k in llama.CFG_KEYS)
    total, grad = _batch_grad(params, tokens, labels, items, quantize)
    n = tokens.size
    return float(total) / n, jax.tree.map(lambda g: g / n, grad)


def lr_at(t: int, o: dict) -> float:
    if t < o["warmup_steps"]:
        return o["base_lr"] * t / max(o["warmup_steps"], 1)
    prog = min(max((t - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    r = o["min_lr_ratio"]
    return o["base_lr"] * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


@jax.jit
def _clip(grads, clip):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


@partial(jax.jit, donate_argnums=(1, 2, 3))
def _adamw(params, grads, m, v, t, lr, b1, b2, eps, wd):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1 / (jnp.sqrt(v / c2) + eps) + wd * p),
        params, m, v)
    return params, m, v


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), tree)


def train(params, batches, cfg: dict, o: dict, quantize: bool = False):
    """Run ``len(batches)`` steps from ``params`` with fresh optimizer state.
    Returns the losses, the per-leaf norms of the first step's clipped
    gradient (a tree of scalars) and the final parameters."""
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches):
        loss, g = loss_and_grad(params, tokens, labels, cfg, quantize)
        g = _clip(g, o["clip_norm"])
        if first is None:
            first = leaf_norms(g)
        params, m, v = _adamw(params, g, m, v, float(i + 1), lr_at(i, o),
                              o["b1"], o["b2"], o["eps"], o["weight_decay"])
        losses.append(loss)
    return losses, first, params
