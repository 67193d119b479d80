"""Block assembly: dense / MoE / SSM / hybrid patterns, scan-over-layers.

Layers are grouped by the config's ``pattern`` period (e.g. RecurrentGemma's
(rec, rec, attn)); parameters for each period position are stacked and the
stack runs under ``lax.scan`` (small HLO, fast compiles at 64 layers) with a
``jax.checkpoint`` remat policy around the period body.  Remainder layers
(n_layers % period) are unrolled.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..mesh.api import ParallelCtx
from .attention import (
    apply_attention,
    attention_specs,
    decode_attention,
    init_attention,
    init_kv_cache,
    kv_cache_specs,
    mixed_attention,
)
from .common import rms_norm
from .mlp import apply_mlp, apply_mlp_replicated, init_mlp, mlp_specs
from .moe import apply_moe, apply_moe_replicated, init_moe, moe_specs
from .rglru import (
    apply_rglru,
    decode_rglru,
    init_rglru,
    init_rglru_cache,
    rglru_cache_specs,
    rglru_specs,
)
from .ssm import (
    apply_ssm,
    decode_ssm,
    init_ssm,
    init_ssm_cache,
    ssm_cache_specs,
    ssm_specs,
)

REMAT_POLICIES = {
    "none": None,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    # dots without batch dims: saves projection outputs but NOT attention
    # score blocks (those carry batch dims) — the memory/compute middle ground
    "dots_nb": lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "nothing": lambda: jax.checkpoint_policies.nothing_saveable,
}


def init_block(key, kind: str, cfg, ctx: ParallelCtx):
    D = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"norm1": jnp.ones((D,))}
    if kind in ("attn", "moe"):
        p["attn"] = init_attention(ks[0], cfg, ctx)
        p["norm2"] = jnp.ones((D,))
        if kind == "attn":
            p["mlp"] = init_mlp(ks[1], cfg, ctx)
        else:
            p["moe"] = init_moe(ks[1], cfg, ctx)
    elif kind == "ssm":
        p["ssm"] = init_ssm(ks[0], cfg, ctx)
    elif kind == "rec":
        p["rec"] = init_rglru(ks[0], cfg, ctx)
        p["norm2"] = jnp.ones((D,))
        p["mlp"] = init_mlp(ks[1], cfg, ctx)
    else:
        raise ValueError(kind)
    return p


def block_specs(kind: str, cfg, ctx: ParallelCtx):
    from jax.sharding import PartitionSpec as P

    sp = {"norm1": P(None)}
    if kind in ("attn", "moe"):
        sp["attn"] = attention_specs(cfg, ctx)
        sp["norm2"] = P(None)
        if kind == "attn":
            sp["mlp"] = mlp_specs(cfg, ctx)
        else:
            sp["moe"] = moe_specs(cfg, ctx)
    elif kind == "ssm":
        sp["ssm"] = ssm_specs(cfg, ctx)
    elif kind == "rec":
        sp["rec"] = rglru_specs(cfg, ctx)
        sp["norm2"] = P(None)
        sp["mlp"] = mlp_specs(cfg, ctx)
    return sp


def apply_block(p, kind: str, x, cfg, ctx: ParallelCtx, *, interp=False):
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "moe"):
        x = x + apply_attention(
            p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, ctx,
            use_kernel_interpret=interp,
        )
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if kind == "attn":
            x = x + apply_mlp(p["mlp"], h, cfg, ctx)
        else:
            y, aux = apply_moe(p["moe"], h, cfg, ctx)
            x = x + y
    elif kind == "ssm":
        x = x + apply_ssm(
            p["ssm"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, ctx,
            use_kernel_interpret=interp,
        )
    elif kind == "rec":
        x = x + apply_rglru(p["rec"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, ctx)
        x = x + apply_mlp(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg, ctx)
    return x, aux


def init_block_cache(kind: str, cfg, B: int, capacity: int, ctx, dtype):
    if kind in ("attn", "moe"):
        cap = capacity if cfg.local_window is None else min(
            capacity, _pow2_pad(cfg.local_window, ctx.tp)
        )
        return init_kv_cache(cfg, B, cap, ctx, dtype)
    if kind == "ssm":
        return init_ssm_cache(cfg, B, ctx, dtype)
    if kind == "rec":
        return init_rglru_cache(cfg, B, ctx, dtype)
    raise ValueError(kind)


def _pow2_pad(w: int, tp: int) -> int:
    return ((w + tp - 1) // tp) * tp


def block_cache_specs(kind: str, ctx, shard_batch: bool = True):
    if kind in ("attn", "moe"):
        return kv_cache_specs(ctx, shard_batch)
    if kind == "ssm":
        return ssm_cache_specs(ctx, shard_batch)
    if kind == "rec":
        return rglru_cache_specs(ctx, shard_batch)
    raise ValueError(kind)


def decode_block(p, kind: str, x, cache, pos, cfg, ctx: ParallelCtx,
                 chunk=None):
    """One decode step of one block; with ``chunk`` (dense attention
    blocks only) the rows past the B decode rows are a prompt chunk
    (:func:`~repro.models.attention.mixed_attention`)."""
    assert chunk is None or kind == "attn", \
        f"no chunked prefill through {kind!r} blocks"
    if kind in ("attn", "moe"):
        xn = rms_norm(x, p["norm1"], cfg.norm_eps)
        if chunk is None:
            y, cache = decode_attention(p["attn"], xn, cache, pos, cfg, ctx)
        else:
            y, cache = mixed_attention(p["attn"], xn, cache, pos, chunk, cfg,
                                       ctx)
        x = x + y
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if kind == "attn":
            x = x + apply_mlp_replicated(p["mlp"], h, cfg, ctx)
        else:
            y2, _ = apply_moe_replicated(p["moe"], h, cfg, ctx)
            x = x + y2
    elif kind == "ssm":
        y, cache = decode_ssm(p["ssm"], rms_norm(x, p["norm1"], cfg.norm_eps), cache, cfg, ctx)
        x = x + y
    elif kind == "rec":
        y, cache = decode_rglru(p["rec"], rms_norm(x, p["norm1"], cfg.norm_eps), cache, cfg, ctx)
        x = x + y
        x = x + apply_mlp_replicated(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg, ctx)
    return x, cache


# ------------------------------------------------------- stacked (scan) form


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_stack(key, cfg, ctx: ParallelCtx):
    """Returns {"periods": stacked-per-position params, "rem": remainder}."""
    pattern = cfg.pattern
    period = len(pattern)
    n_full = cfg.n_layers // period
    rem = cfg.n_layers % period
    keys = jax.random.split(key, cfg.n_layers)
    periods = []
    for i in range(n_full):
        periods.append(
            tuple(
                init_block(keys[i * period + j], pattern[j], cfg, ctx)
                for j in range(period)
            )
        )
    stacked = _stack_trees(periods) if n_full > 0 else None
    remainder = tuple(
        init_block(keys[n_full * period + j], pattern[j], cfg, ctx)
        for j in range(rem)
    )
    return {"periods": stacked, "rem": remainder}


def stack_specs(cfg, ctx: ParallelCtx):
    from jax.sharding import PartitionSpec as P

    pattern = cfg.pattern
    period = len(pattern)
    n_full = cfg.n_layers // period
    rem = cfg.n_layers % period

    def prepend(spec_tree):
        return jax.tree.map(
            lambda s: P(*((None,) + tuple(s))),
            spec_tree,
            is_leaf=lambda s: isinstance(s, P),
        )

    stacked = (
        tuple(prepend(block_specs(pattern[j], cfg, ctx)) for j in range(period))
        if n_full > 0 else None
    )
    remainder = tuple(block_specs(pattern[j], cfg, ctx) for j in range(rem))
    return {"periods": stacked, "rem": remainder}


def _shift_plan(plan):
    """Stacked-storage FSDP dims -> per-slice dims (scan strips dim 0)."""
    return jax.tree.map(lambda d: d - 1 if d > 0 else -1, plan)


def apply_stack(params, x, cfg, ctx: ParallelCtx, *, interp=False, remat="dots",
                fsdp_plan=None):
    from ..mesh.api import fsdp_gather

    pattern = cfg.pattern
    period = len(pattern)
    period_plan = (
        _shift_plan(fsdp_plan["periods"])
        if fsdp_plan is not None and fsdp_plan["periods"] is not None else None
    )

    def period_fn(x, pp):
        if period_plan is not None:
            # ZeRO-3 weight streaming: gather this period's layer params
            # (AD transposes to the reduce-scatter grad sync)
            pp = fsdp_gather(pp, period_plan, ctx)
        aux = jnp.zeros((), jnp.float32)
        for j in range(period):
            x, a = apply_block(pp[j], pattern[j], x, cfg, ctx, interp=interp)
            aux = aux + a
        return x, aux

    body = period_fn
    if remat != "none":
        policy = REMAT_POLICIES[remat]()
        body = jax.checkpoint(period_fn, policy=policy)

    aux_total = jnp.zeros((), jnp.float32)
    if params["periods"] is not None:
        x, auxs = lax.scan(lambda c, pp: body(c, pp), x, params["periods"])
        aux_total = aux_total + auxs.sum()
    for j, p in enumerate(params["rem"]):
        if fsdp_plan is not None:
            p = fsdp_gather(p, fsdp_plan["rem"][j], ctx)
        x, a = apply_block(p, pattern[j], x, cfg, ctx, interp=interp)
        aux_total = aux_total + a
    return x, aux_total


def init_stack_cache(cfg, B: int, capacity: int, ctx, dtype):
    pattern = cfg.pattern
    period = len(pattern)
    n_full = cfg.n_layers // period
    rem = cfg.n_layers % period
    stacked = (
        _stack_trees(
            [
                tuple(
                    init_block_cache(pattern[j], cfg, B, capacity, ctx, dtype)
                    for j in range(period)
                )
                for _ in range(n_full)
            ]
        )
        if n_full > 0 else None
    )
    remainder = tuple(
        init_block_cache(pattern[j], cfg, B, capacity, ctx, dtype)
        for j in range(rem)
    )
    return {"periods": stacked, "rem": remainder}


def stack_cache_specs(cfg, ctx, shard_batch: bool = True):
    from jax.sharding import PartitionSpec as P

    pattern = cfg.pattern
    period = len(pattern)
    n_full = cfg.n_layers // period
    rem = cfg.n_layers % period

    def prepend(spec_tree):
        return jax.tree.map(
            lambda s: P(*((None,) + tuple(s))),
            spec_tree,
            is_leaf=lambda s: isinstance(s, P),
        )

    stacked = (
        tuple(prepend(block_cache_specs(pattern[j], ctx, shard_batch))
              for j in range(period))
        if n_full > 0 else None
    )
    remainder = tuple(
        block_cache_specs(pattern[j], ctx, shard_batch) for j in range(rem)
    )
    return {"periods": stacked, "rem": remainder}


def decode_stack(params, caches, x, pos, cfg, ctx: ParallelCtx, *, fsdp_plan=None,
                 chunk=None):
    from ..mesh.api import fsdp_gather

    pattern = cfg.pattern
    period = len(pattern)
    period_plan = (
        _shift_plan(fsdp_plan["periods"])
        if fsdp_plan is not None and fsdp_plan["periods"] is not None else None
    )

    def period_fn(x, pp_cc):
        pp, cc = pp_cc
        if period_plan is not None:
            pp = fsdp_gather(pp, period_plan, ctx)
        new_cc = []
        for j in range(period):
            x, c = decode_block(pp[j], pattern[j], x, cc[j], pos, cfg, ctx,
                                chunk)
            new_cc.append(c)
        return x, tuple(new_cc)

    if params["periods"] is not None:
        x, new_stacked = lax.scan(
            period_fn, x, (params["periods"], caches["periods"])
        )
    else:
        new_stacked = None
    new_rem = []
    for j, p in enumerate(params["rem"]):
        if fsdp_plan is not None:
            p = fsdp_gather(p, fsdp_plan["rem"][j], ctx)
        x, c = decode_block(p, pattern[j], x, caches["rem"][j], pos, cfg, ctx,
                            chunk)
        new_rem.append(c)
    return x, {"periods": new_stacked, "rem": tuple(new_rem)}
