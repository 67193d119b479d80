"""LM wrapper: embeddings -> block stack -> head/loss; prefill + decode.

Sequence-parallel residual stream end-to-end:
  * vocab-parallel embedding with the psum fused into a reduce-scatter onto
    sequence shards (Megatron-SP style; SMI or bulk collectives, with the
    SMI wire path selected by the ctx transport backend — comm_mode
    "smi:static" | "smi:packet" | "smi:fused", see repro/transport),
  * vocab-parallel cross-entropy, chunked over the sequence so (B, S, V/tp)
    logits never materialise at once,
  * modality frontends per the assignment: VLM patch embeddings and
    EnCodec codebook streams arrive precomputed via input_specs() stubs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..mesh.api import ParallelCtx
from ..parallel import (
    gather_sequence,
    parallel_embedding_partial,
    psum_tagged,
    reduce_scatter_sequence,
    vocab_parallel_cross_entropy,
)
from .common import lm_head, rms_norm, trunc_normal
from .transformer import (
    apply_stack,
    decode_stack,
    init_stack,
    init_stack_cache,
    stack_cache_specs,
    stack_specs,
)


def _v_loc(cfg, tp: int) -> int:
    assert cfg.padded_vocab % tp == 0 or tp == 1
    return cfg.padded_vocab // tp if tp > 1 else cfg.padded_vocab


def init_lm(key, cfg, ctx: ParallelCtx):
    """GLOBAL-shape LM params (vocab padded; sharded by lm_specs)."""
    D = cfg.d_model
    V = cfg.padded_vocab
    assert V % ctx.tp == 0 or ctx.tp == 1
    ks = jax.random.split(key, 4)
    p = {"final_norm": jnp.ones((D,)), "stack": init_stack(ks[1], cfg, ctx)}
    if cfg.n_codebooks > 1:
        p["embed_cb"] = trunc_normal(ks[0], (cfg.n_codebooks, V, D), 0.02)
        p["head_cb"] = trunc_normal(ks[2], (cfg.n_codebooks, D, V), D ** -0.5)
    else:
        p["embed"] = trunc_normal(ks[0], (V, D), 0.02)
        if not cfg.tie_embeddings:
            p["head"] = trunc_normal(ks[2], (D, V), D ** -0.5)
    return p


def lm_specs(cfg, ctx: ParallelCtx):
    from jax.sharding import PartitionSpec as P

    m = ctx.model_axis
    sp = {"final_norm": P(None), "stack": stack_specs(cfg, ctx)}
    if cfg.n_codebooks > 1:
        sp["embed_cb"] = P(None, m, None)
        sp["head_cb"] = P(None, None, m)
    else:
        sp["embed"] = P(m, None)
        if not cfg.tie_embeddings:
            sp["head"] = P(None, m)
    return sp


def _cast(p, dtype):
    return jax.tree.map(
        lambda v: v.astype(dtype) if v.dtype == jnp.float32 else v, p
    )


# --------------------------------------------------------------- embedding


def _embed_partial(table_local, ids, ctx: ParallelCtx):
    """Local-vocab-shard partial embedding, NO reduction (caller picks
    psum for decode or reduce-scatter for the SP residual stream)."""
    return parallel_embedding_partial(table_local, ids, ctx)


def embed_tokens_sp(params, tokens, cfg, ctx: ParallelCtx, extra_embeds=None):
    """tokens: (B, S) (or (B, S, n_cb)) replicated -> (B, S_loc, D) shards."""
    tp = ctx.tp
    if cfg.n_codebooks > 1:
        emb = sum(
            _embed_partial(params["embed_cb"][cb], tokens[..., cb], ctx)
            for cb in range(cfg.n_codebooks)
        )
    else:
        emb = _embed_partial(params["embed"], tokens, ctx)
    B, S = emb.shape[0], emb.shape[1]
    if extra_embeds is not None:
        # VLM stub: first n_patches positions are precomputed patch embeds.
        npch = extra_embeds.shape[1]
        # zero the partial for patch positions; add them post-reduction so
        # only one vocab shard (rank 0) contributes the full value
        pos = jnp.arange(S)[None, :, None]
        emb = jnp.where(pos < npch, 0.0, emb)
        pad = jnp.zeros((B, S - npch, emb.shape[-1]), extra_embeds.dtype)
        full = jnp.concatenate([extra_embeds, pad], axis=1)
        emb = emb + jnp.where(
            jnp.logical_and(pos < npch, ctx.rank() == 0), full, 0.0
        )
    if tp > 1:
        # fused vocab-psum + seq-scatter: reduce_scatter over blocks laid out
        # shard-major: (tp, B, S_loc, D) flattened on rows
        S_loc = S // tp
        blocks = (
            emb.reshape(B, tp, S_loc, -1).transpose(1, 0, 2, 3)
            .reshape(tp * B * S_loc, -1)
        )
        out = reduce_scatter_sequence(blocks, ctx, tag="tp.embed")
        return out.reshape(B, S_loc, -1).astype(_dt(cfg))
    return emb.astype(_dt(cfg))


def _dt(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ------------------------------------------------------------ train / loss


def _step_params(params, cfg, ctx: ParallelCtx, fsdp_plan):
    """The weights one step reads: cast once to the compute
    dtype, the top-level leaves gathered under an FSDP plan."""
    pf = _cast(params, _dt(cfg))
    if fsdp_plan is not None:
        from ..mesh.api import fsdp_gather

        for key in ("embed", "head", "embed_cb", "head_cb", "final_norm"):
            if key in pf:
                pf[key] = fsdp_gather(pf[key], fsdp_plan[key], ctx)
    return pf


def lm_loss(
    params,
    tokens,      # (B, S) int32 (or (B, S, n_cb))
    labels,      # same shape; -100 = ignore
    cfg,
    ctx: ParallelCtx,
    *,
    extra_embeds=None,
    interp=False,
    remat="dots",
    loss_chunks: int = 1,
    aux_weight: float = 1e-2,
    fsdp_plan=None,
):
    """Causal-LM loss (mean CE over valid labels) + MoE aux loss."""
    tp = ctx.tp
    pf = _step_params(params, cfg, ctx, fsdp_plan)
    x = embed_tokens_sp(pf, tokens, cfg, ctx, extra_embeds=extra_embeds)
    x, aux = apply_stack(pf["stack"], x, cfg, ctx, interp=interp, remat=remat,
                         fsdp_plan=None if fsdp_plan is None else fsdp_plan["stack"])
    x = rms_norm(x, pf["final_norm"], cfg.norm_eps)      # (B, S_loc, D)

    B, S_loc, D = x.shape
    S = S_loc * tp

    if cfg.n_codebooks > 1:
        tables = [pf["head_cb"][cb] for cb in range(cfg.n_codebooks)]
    elif cfg.tie_embeddings:
        tables = [pf["embed"].T]
    else:
        tables = [pf["head"]]

    assert S_loc % loss_chunks == 0
    csz = S_loc // loss_chunks
    total = jnp.zeros((), jnp.float32)
    count = jnp.zeros((), jnp.float32)

    def chunk_ce(xc, labc):
        """xc: (B, csz, D) shard chunk; labc: (B, tp*csz[, n_cb]) aligned."""
        if tp > 1:
            xg = gather_sequence(xc.reshape(B * csz, D), ctx,
                                 tag="tp.loss.gather")
            xg = xg.reshape(tp, B, csz, D).transpose(1, 0, 2, 3).reshape(B, tp * csz, D)
        else:
            xg = xc
        t = jnp.zeros((), jnp.float32)
        c = jnp.zeros((), jnp.float32)
        for cb, table in enumerate(tables):
            logits = jnp.einsum("bsd,dv->bsv", xg, table).astype(jnp.float32)
            lab = labc[..., cb] if cfg.n_codebooks > 1 else labc
            valid = lab >= 0
            ce = vocab_parallel_cross_entropy(logits, jnp.maximum(lab, 0), ctx)
            t = t + jnp.sum(jnp.where(valid, ce, 0.0))
            c = c + jnp.sum(valid.astype(jnp.float32))
        return t, c

    chunk_ce_r = jax.checkpoint(chunk_ce) if loss_chunks > 1 else chunk_ce

    for ci in range(loss_chunks):
        xc = lax.dynamic_slice_in_dim(x, ci * csz, csz, axis=1)
        if tp > 1:
            # labels for the gathered chunk: (B, tp, csz) -> (B, tp*csz),
            # r-major blocks matching the all-gathered x layout
            lb = labels.reshape((B, tp, S_loc) + labels.shape[2:])
            lb = lax.dynamic_slice_in_dim(lb, ci * csz, csz, axis=2)
            lb = lb.reshape((B, tp * csz) + labels.shape[2:])
        else:
            lb = lax.dynamic_slice_in_dim(labels, ci * csz, csz, axis=1)
        t, c = chunk_ce_r(xc, lb)
        total = total + t
        count = count + c

    loss = total / jnp.maximum(count, 1.0)
    return loss + aux_weight * aux, (loss, aux)


# ---------------------------------------------------------------- serving


def lm_prefill(params, tokens, cfg, ctx: ParallelCtx, *, capacity: int,
               extra_embeds=None, interp=False, fsdp_plan=None):
    """Prefill: full forward (no caches materialised — SMI streaming keeps
    attention block-wise); returns final hidden states, sequence-sharded.

    NOTE: serving-grade prefill would also populate the KV cache; the
    continuous engine builds it in prompt chunks that ride in its decode
    step (:func:`lm_mixed_step`), or by replaying the prompt through
    decode steps, while the 32k prefill shape benchmarks this compute
    path.
    """
    pf = _step_params(params, cfg, ctx, fsdp_plan)
    x = embed_tokens_sp(pf, tokens, cfg, ctx, extra_embeds=extra_embeds)
    x, _ = apply_stack(pf["stack"], x, cfg, ctx, interp=interp, remat="none",
                       fsdp_plan=None if fsdp_plan is None else fsdp_plan["stack"])
    return rms_norm(x, pf["final_norm"], cfg.norm_eps)


def _serve_logits(pf, x, cfg, ctx: ParallelCtx, gather_logits: bool):
    """Head of the serving steps: x (N, D) final hidden rows -> f32
    logits, full (N, V[, n_cb]) when ``gather_logits``, else the local
    vocab shard (N, V_loc[, n_cb])."""
    if cfg.n_codebooks > 1:
        logit_loc = jnp.stack(
            [x @ pf["head_cb"][cb] for cb in range(cfg.n_codebooks)], axis=-1
        )  # (N, V_loc, n_cb)
    elif cfg.tie_embeddings:
        logit_loc = x @ pf["embed"].T
    else:
        logit_loc = x @ pf["head"]
    if not gather_logits:
        return logit_loc.astype(jnp.float32)
    # gather the vocab shards: (V_loc, ...) -> (V, ...)
    logits = gather_sequence(jnp.moveaxis(logit_loc, 1, 0), ctx,
                             tag="tp.loss.gather")
    logits = jnp.moveaxis(logits, 0, 1)                     # (N, V[, n_cb])
    return logits.astype(jnp.float32)


def lm_decode_step(params, caches, token, pos, cfg, ctx: ParallelCtx,
                   *, gather_logits: bool = True, fsdp_plan=None):
    """One decode step.  token: (B,) int32 (or (B, n_cb)); pos: scalar.

    Returns (logits, caches'): full (B, V[, n_cb]) when ``gather_logits``,
    else the local vocab shard (B, V_loc[, n_cb]) for shard_map out_specs
    to assemble (avoids the in-region gather)."""
    pf = _step_params(params, cfg, ctx, fsdp_plan)
    if cfg.n_codebooks > 1:
        emb = sum(
            _embed_partial(pf["embed_cb"][cb], token[:, cb], ctx)
            for cb in range(cfg.n_codebooks)
        )
    else:
        emb = _embed_partial(pf["embed"], token, ctx)
    x = psum_tagged(emb, ctx, "tp.embed")[:, None, :].astype(_dt(cfg))  # (B, 1, D)
    x, caches = decode_stack(pf["stack"], caches, x, pos, cfg, ctx,
                             fsdp_plan=None if fsdp_plan is None else fsdp_plan["stack"])
    x = rms_norm(x, pf["final_norm"], cfg.norm_eps)[:, 0]   # (B, D)
    return _serve_logits(pf, x, cfg, ctx, gather_logits), caches


def chunked_prefill_ok(cfg) -> bool:
    """Whether :func:`lm_mixed_step` can take a prompt in chunks: every
    layer is dense attention over the whole context with one token
    stream.  Recurrent state (SSM, RG-LRU) takes its tokens one at a time,
    a window's ring is smaller than a chunk may reach, MoE capacity drops
    depend on the batch's rows, and codebook streams have no chunk
    input."""
    return (all(kind == "attn" for kind in cfg.layer_pattern)
            and cfg.local_window is None and cfg.n_codebooks == 1)


def lm_mixed_step(params, caches, token, pos, chunk_tok, chunk_slot,
                  chunk_start, chunk_len, cfg, ctx: ParallelCtx,
                  *, gather_logits: bool = True, fsdp_plan=None):
    """One decode step for the B slots plus one prompt chunk for one slot,
    in the same matmuls (B + C rows; for :func:`chunked_prefill_ok`
    configs).

    token, pos: (B,) as for :func:`lm_decode_step`, except that a slot
    whose ``pos`` is negative is not decoding and writes nothing to the
    cache.  chunk_tok: (C,) prompt tokens, the first ``chunk_len`` real,
    for slot ``chunk_slot`` at positions ``chunk_start ...`` (below the
    capacity).  Only the chunk's last real row goes through the head.

    Returns (logits (B, V), chunk_logits (V,), caches'), the local vocab
    shards (V_loc) unless ``gather_logits``."""
    B = token.shape[0]
    pf = _step_params(params, cfg, ctx, fsdp_plan)
    emb = _embed_partial(pf["embed"], jnp.concatenate([token, chunk_tok]), ctx)
    x = psum_tagged(emb, ctx, "tp.embed")[:, None, :].astype(_dt(cfg))
    x, caches = decode_stack(
        pf["stack"], caches, x, pos, cfg, ctx,
        fsdp_plan=None if fsdp_plan is None else fsdp_plan["stack"],
        chunk=(chunk_slot, chunk_start, chunk_len),
    )                                                       # (B + C, 1, D)
    rows = jnp.concatenate(
        [x[:B, 0], lax.dynamic_index_in_dim(x[:, 0], B + chunk_len - 1, 0)])
    rows = rms_norm(rows, pf["final_norm"], cfg.norm_eps)   # (B + 1, D)
    logits = _serve_logits(pf, rows, cfg, ctx, gather_logits)
    return logits[:B], logits[B], caches


def lm_caches(cfg, B: int, capacity: int, ctx: ParallelCtx):
    return init_stack_cache(cfg, B, capacity, ctx, _dt(cfg))


def lm_cache_specs(cfg, ctx: ParallelCtx, shard_batch: bool = True):
    return stack_cache_specs(cfg, ctx, shard_batch)
