"""GQA attention: TP/SP train + prefill, distributed flash-decode.

TP layout at tp-way model parallelism (all derived from the assignment's
head counts, which are never divisible by 16 in the KV dimension):

* wq, wo — head-sharded; the head count is padded up to a multiple of tp
  and padded heads are hard-masked (zero output, zero gradient).
* wk, wv — **replicated** (every arch here has n_kv <= 24 < 2*tp; this is
  the standard GQA-under-TP arrangement: KV is cheap, queries are not).
* prefill/train: sequence-parallel residual stream; column-parallel QKV via
  streamed allgather-matmul, row-parallel output via streamed
  matmul-reduce-scatter (the SMI overlap engine).
* decode: KV cache sharded over the model axis on the *sequence* dim
  (uniform regardless of kv head count); queries all-gathered (tiny) and
  flash-decoding LSE-combine psum'd over the model axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import flash_attention
from ..mesh.api import ParallelCtx
from ..parallel import (
    column_parallel_linear,
    gather_sequence,
    pmax_tagged,
    psum_tagged,
    ring_attention,
    row_parallel_linear,
)
from .common import rms_norm, rope, rope_batched, trunc_normal


def _pad_heads(H: int, tp: int) -> int:
    return ((H + tp - 1) // tp) * tp


def init_attention(key, cfg, ctx: ParallelCtx):
    """GLOBAL-shape attention params (sharded onto devices by the specs;
    head count padded to the TP degree, padded heads hard-masked)."""
    D, hd = cfg.d_model, cfg.hd
    tp = ctx.tp
    Hp = _pad_heads(cfg.n_heads, tp)
    ks = jax.random.split(key, 6)
    s_in = D ** -0.5
    p = {
        "wq": trunc_normal(ks[0], (D, Hp * hd), s_in),
        "wk": trunc_normal(ks[1], (D, cfg.n_kv_heads * hd), s_in),
        "wv": trunc_normal(ks[2], (D, cfg.n_kv_heads * hd), s_in),
        "wo": trunc_normal(ks[3], (Hp * hd, D), (Hp * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hp * hd,))
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,))
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,))
        p["k_norm"] = jnp.ones((hd,))
    return p


def attention_specs(cfg, ctx: ParallelCtx):
    from jax.sharding import PartitionSpec as P

    m = ctx.model_axis
    sp = {
        "wq": P(None, m),
        "wk": P(None, None),
        "wv": P(None, None),
        "wo": P(m, None),
    }
    if cfg.qkv_bias:
        sp["bq"] = P(m)
        sp["bk"] = P(None)
        sp["bv"] = P(None)
    if cfg.qk_norm:
        sp["q_norm"] = P(None)
        sp["k_norm"] = P(None)
    return sp


def _head_mask_and_kv_map(cfg, ctx: ParallelCtx):
    """(H_loc,) mask of real heads + (H_loc,) kv-head index per local head."""
    tp = ctx.tp
    Hp = _pad_heads(cfg.n_heads, tp)
    H_loc = Hp // tp
    g = max(cfg.n_heads // cfg.n_kv_heads, 1)
    r = ctx.rank()
    gh = r * H_loc + jnp.arange(H_loc)            # global head ids
    mask = (gh < cfg.n_heads).astype(jnp.float32)
    kv_idx = jnp.clip(gh // g, 0, cfg.n_kv_heads - 1)
    return mask, kv_idx


def apply_attention_ring(p, x, cfg, ctx: ParallelCtx):
    """Ring-attention block (beyond-paper §Perf): the sequence stays sharded
    and the (small, GQA) K/V blocks stream around the ring instead of the
    (large) activations — per-layer attention wire bytes drop by
    D / (2 * n_kv * hd) (= 4x for yi-6b, 8x for glm4-9b).

    The head-sharded wq/wo are all-gathered over the model axis first (a
    few 10s of MB — amortised against the saved activation rings); each
    device then computes ALL heads for ITS sequence shard, so compute stays
    balanced and no reduce-scatter is needed at the output.
    """
    B, S_loc, D = x.shape
    tp = ctx.tp
    hd = cfg.hd
    H_loc = p["wq"].shape[1] // hd
    Hp = H_loc * tp
    r = ctx.rank()

    # gather the head-sharded weights (small) over the model ring
    if tp > 1:
        wq = gather_sequence(jnp.moveaxis(p["wq"], 1, 0), ctx, tag="tp.attn.qkv")
        wq = jnp.moveaxis(wq, 0, 1)                  # (D, Hp*hd)
        wo = gather_sequence(p["wo"], ctx, tag="tp.attn.out")  # (Hp*hd, D)
        bq = (gather_sequence(p["bq"], ctx, tag="tp.attn.qkv")
              if cfg.qkv_bias else None)
    else:
        wq, wo = p["wq"], p["wo"]
        bq = p.get("bq")

    x2d = x.reshape(B * S_loc, D)
    q = x2d @ wq
    k = x2d @ p["wk"]
    v = x2d @ p["wv"]
    if cfg.qkv_bias:
        q = q + bq
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S_loc, Hp, hd)
    k = k.reshape(B, S_loc, cfg.n_kv_heads, hd)
    v = v.reshape(B, S_loc, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    pos = r * S_loc + jnp.arange(S_loc)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    if tp > 1:
        o = ring_attention(
            q, k, v, ctx, tag="tp.attn.ring", causal=True,
            local_window=cfg.local_window,
        )                                             # (B, S_loc, Hp, hd)
    else:
        from ..kernels import flash_attention

        g = max(cfg.n_heads // cfg.n_kv_heads, 1)
        kv_idx = jnp.clip(jnp.arange(Hp) // g, 0, cfg.n_kv_heads - 1)
        o = flash_attention(q, jnp.take(k, kv_idx, 2), jnp.take(v, kv_idx, 2),
                            causal=True, window=cfg.local_window)
    head_ok = (jnp.arange(Hp) < cfg.n_heads).astype(o.dtype)
    o = o * head_ok[None, None, :, None]
    y = o.reshape(B * S_loc, Hp * hd) @ wo            # local rows: no RS
    return y.reshape(B, S_loc, D)


def apply_attention(p, x, cfg, ctx: ParallelCtx, *, use_kernel_interpret=False):
    """Train/prefill.  x: (B, S_loc, D) sequence-sharded; returns same."""
    if getattr(ctx, "opt_ring_attn", False):
        return apply_attention_ring(p, x, cfg, ctx)
    B, S_loc, D = x.shape
    tp = ctx.tp
    S = S_loc * tp
    hd = cfg.hd
    H_loc = p["wq"].shape[1] // hd
    mask, kv_idx = _head_mask_and_kv_map(cfg, ctx)

    x2d = x.reshape(B * S_loc, D)
    # column-parallel Q (head-sharded); replicated KV
    if ctx.opt_shared_gather:
        # one ring: Q overlapped with the gather; KV from the free copy
        q, xf = column_parallel_linear(
            x2d, p["wq"], ctx, tag="tp.attn.qkv", return_gathered=True
        )
    else:
        q = column_parallel_linear(
            x2d, p["wq"], ctx, tag="tp.attn.qkv"
        )                                             # (tp*B*S_loc, H_loc*hd)
        xf = gather_sequence(x2d, ctx, tag="tp.attn.kv") if tp > 1 else x2d
    k = xf @ p["wk"]
    v = xf @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]

    def to_bshd(t, H):
        return (
            t.reshape(tp, B, S_loc, H, hd)
            .transpose(1, 0, 2, 3, 4)
            .reshape(B, S, H, hd)
        )

    q = to_bshd(q, H_loc)
    k = to_bshd(k, cfg.n_kv_heads)
    v = to_bshd(v, cfg.n_kv_heads)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    pos = jnp.arange(S)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    # local q heads attend their mapped kv head (gather once; GQA under TP)
    k_sel = jnp.take(k, kv_idx, axis=2)               # (B, S, H_loc, hd)
    v_sel = jnp.take(v, kv_idx, axis=2)
    o = flash_attention(
        q, k_sel, v_sel,
        causal=True, window=cfg.local_window,
        interpret=use_kernel_interpret,
    )                                                  # (B, S, H_loc, hd)
    o = o * mask[None, None, :, None].astype(o.dtype)
    # row-parallel out projection, reduce-scatter back to sequence shards
    o2d = (
        o.reshape(B, tp, S_loc, H_loc, hd)
        .transpose(1, 0, 2, 3, 4)
        .reshape(tp * B * S_loc, H_loc * hd)
    )
    y = row_parallel_linear(o2d, p["wo"], ctx, tag="tp.attn.out")  # (B*S_loc, D)
    return y.reshape(B, S_loc, D)


# ------------------------------------------------------------------ decode


def init_kv_cache(cfg, B_loc: int, capacity: int, ctx: ParallelCtx, dtype):
    """Sequence-sharded ring cache: (B, cap/tp, Hkv, hd) + slot positions."""
    tp = ctx.tp
    cap_loc = capacity // tp
    return {
        "k": jnp.zeros((B_loc, cap_loc, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((B_loc, cap_loc, cfg.n_kv_heads, cfg.hd), dtype),
        "slot_pos": jnp.full((B_loc, cap_loc), -1, jnp.int32),
    }


def kv_cache_specs(ctx: ParallelCtx, shard_batch: bool = True):
    from jax.sharding import PartitionSpec as P

    m = ctx.model_axis
    b = None
    if shard_batch and ctx.batch_axes:
        b = ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]
    return {"k": P(b, m, None, None), "v": P(b, m, None, None),
            "slot_pos": P(b, m)}


def _decode_qkv(p, x2d, pos_b, cfg, ctx: ParallelCtx):
    """Projections of N single-token rows at per-row positions ``pos_b``
    (N,): every query head (all-gathered over the model axis), and the
    rows' new K/V.  Returns (q (N, Hp, hd), k_new, v_new (N, 1, Hkv, hd))."""
    N = x2d.shape[0]
    hd = cfg.hd
    tp = ctx.tp
    H_loc = p["wq"].shape[1] // hd
    Hp = H_loc * tp
    q_loc = (x2d @ p["wq"])
    k_new = (x2d @ p["wk"])
    v_new = (x2d @ p["wv"])
    if cfg.qkv_bias:
        q_loc = q_loc + p["bq"]
        k_new = k_new + p["bk"]
        v_new = v_new + p["bv"]
    q_loc = q_loc.reshape(N, 1, H_loc, hd)
    k_new = k_new.reshape(N, 1, cfg.n_kv_heads, hd)
    v_new = v_new.reshape(N, 1, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q_loc = rms_norm(q_loc, p["q_norm"], cfg.norm_eps)
        k_new = rms_norm(k_new, p["k_norm"], cfg.norm_eps)
    q_loc = rope_batched(q_loc, pos_b, cfg.rope_theta)
    k_new = rope_batched(k_new, pos_b, cfg.rope_theta)

    # gather all query heads (tiny) so every device scans its cache slice
    if tp > 1:
        q = gather_sequence(q_loc.reshape(N, H_loc * hd)[None], ctx,
                            tag="tp.attn.qkv")
        q = q.reshape(tp, N, H_loc, hd).transpose(1, 0, 2, 3).reshape(N, Hp, hd)
    else:
        q = q_loc.reshape(N, Hp, hd)
    return q, k_new, v_new


def _ring_write(cache, k_new, v_new, pos_b, ctx: ParallelCtx, writes=None):
    """Ring-buffer write, per batch row: global slot = pos % capacity;
    shard r owns slots [r*cap_loc, (r+1)*cap_loc).  A row whose ``writes``
    entry is False leaves the cache as it was."""
    r = ctx.rank()
    cap_loc = cache["k"].shape[1]
    capacity = cap_loc * ctx.tp
    g_slot_b = pos_b % capacity
    my_b = jnp.logical_and(g_slot_b >= r * cap_loc, g_slot_b < (r + 1) * cap_loc)
    if writes is not None:
        my_b = jnp.logical_and(my_b, writes)
    l_slot_b = jnp.clip(g_slot_b - r * cap_loc, 0, cap_loc - 1)
    write = jnp.logical_and(
        my_b[:, None], jnp.arange(cap_loc)[None, :] == l_slot_b[:, None]
    )                                                        # (B, cap_loc)
    k_cache = jnp.where(
        write[:, :, None, None], k_new.astype(cache["k"].dtype), cache["k"]
    )
    v_cache = jnp.where(
        write[:, :, None, None], v_new.astype(cache["v"].dtype), cache["v"]
    )
    slot_pos = jnp.where(write, pos_b[:, None], cache["slot_pos"])
    return {"k": k_cache, "v": v_cache, "slot_pos": slot_pos}


def _masked_softmax_attend(s, valid, v, spec: str, ctx: ParallelCtx):
    """Flash-decoding combine of f32 scores ``s`` (..., k) over this
    shard's cache rows: masked softmax with the max and the sums psum'd
    over the model axis, then ``einsum(spec, p, v)``, normalised."""
    s = jnp.where(valid, s, -1e30)
    m_loc = s.max(axis=-1)
    m_g = pmax_tagged(m_loc, ctx, "tp.attn.out")
    pexp = jnp.exp(s - m_g[..., None])
    pexp = jnp.where(valid, pexp, 0.0)
    l_loc = pexp.sum(axis=-1)
    o_loc = jnp.einsum(spec, pexp, v)
    l_g = psum_tagged(l_loc, ctx, "tp.attn.out")
    o_g = psum_tagged(o_loc, ctx, "tp.attn.out")
    return o_g / jnp.maximum(l_g, 1e-30)[..., None]


def _decode_rows_attend(q, cache, pos_b, cfg, ctx: ParallelCtx):
    """Each row's query (B, Hp, hd) over its own slot's local cache slice,
    rows at positions <= its own (and inside the window, if any)."""
    hd = cfg.hd
    Hp = q.shape[1]
    # partial attention over the local cache slice, all heads
    kv_sel_k = jnp.take(cache["k"], kv_idx_full(cfg, Hp), axis=2)  # (B, cap_loc, Hp, hd)
    kv_sel_v = jnp.take(cache["v"], kv_idx_full(cfg, Hp), axis=2)
    s = jnp.einsum(
        "bhd,bkhd->bhk", q.astype(jnp.float32) * hd ** -0.5,
        kv_sel_k.astype(jnp.float32),
    )
    slot_pos = cache["slot_pos"]
    valid = slot_pos >= 0                                    # (B, cap_loc)
    valid = jnp.logical_and(valid, slot_pos <= pos_b[:, None])
    if cfg.local_window is not None:
        valid = jnp.logical_and(
            valid, slot_pos > pos_b[:, None] - cfg.local_window
        )
    return _masked_softmax_attend(s, valid[:, None, :],
                                  kv_sel_v.astype(jnp.float32),
                                  "bhk,bkhd->bhd", ctx)      # (B, Hp, hd)


def _out_proj(p, o, dtype, cfg, ctx: ParallelCtx):
    """Row-parallel out projection of (N, Hp, hd) heads: padded heads
    zeroed, my head slice only, then psum.  Returns (N, 1, D)."""
    N, Hp, hd = o.shape
    H_loc = Hp // ctx.tp
    o = o * mask_full(cfg, Hp)[None, :, None].astype(o.dtype)
    o_my = lax.dynamic_slice_in_dim(o, ctx.rank() * H_loc, H_loc, axis=1)
    y = (o_my.reshape(N, H_loc * hd).astype(dtype)) @ p["wo"]
    y = psum_tagged(y, ctx, "tp.attn.out")
    return y.reshape(N, 1, -1)


def decode_attention(p, x, cache, pos, cfg, ctx: ParallelCtx):
    """One decode step.  x: (B, 1, D) replicated over model; ``pos`` is the
    absolute position of the new token — a scalar (wave decoding: every
    row at the same position) or a (B,) int array (continuous batching:
    one position per slot).  Returns (y (B, 1, D), cache')."""
    B = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k_new, v_new = _decode_qkv(p, x.reshape(B, -1), pos_b, cfg, ctx)
    cache = _ring_write(cache, k_new, v_new, pos_b, ctx)
    o = _decode_rows_attend(q, cache, pos_b, cfg, ctx)
    return _out_proj(p, o, x.dtype, cfg, ctx), cache


def _chunk_write(cache, k_new, v_new, slot, start, n, ctx: ParallelCtx):
    """Write a prompt chunk's first ``n`` K/V rows (C, Hkv, hd) into slot
    ``slot`` at positions ``start ...``, which lie below the capacity (no
    row wraps the ring).  Each shard rewrites one window of min(C,
    cap_loc) of its own rows in that slot (``dynamic_update_slice``),
    holding the part of the chunk that falls in its row range; the rows
    past ``n`` and every other slot stay as they were."""
    C = k_new.shape[0]
    cap_loc = cache["k"].shape[1]
    W = min(C, cap_loc)
    lo = ctx.rank() * cap_loc                     # this shard's first row
    off = jnp.clip(start - lo, 0, cap_loc - W)    # the window's first row
    row_pos = lo + off + jnp.arange(W)            # positions it holds
    j = row_pos - start                           # chunk row of each
    ok = jnp.logical_and(j >= 0, j < n)
    jc = jnp.clip(j, 0, C - 1)

    def put(leaf, new):
        at = (slot, off) + (0,) * (leaf.ndim - 2)
        old = lax.dynamic_slice(leaf, at, (1, W) + leaf.shape[2:])
        keep = ok.reshape((1, W) + (1,) * (leaf.ndim - 2))
        row = jnp.where(keep, new[None].astype(leaf.dtype), old)
        return lax.dynamic_update_slice(leaf, row, at)

    return {"k": put(cache["k"], jnp.take(k_new, jc, axis=0)),
            "v": put(cache["v"], jnp.take(v_new, jc, axis=0)),
            "slot_pos": put(cache["slot_pos"], row_pos)}


def _chunk_attend(q, cache, slot, start, cfg, ctx: ParallelCtx):
    """Causal attention of a prompt chunk's queries (C, Hp, hd), at
    positions ``start ...``, over slot ``slot``'s local cache rows (which
    already hold the chunk).  GQA by grouping the queries of one KV head,
    so the slot's K/V are read once, unexpanded."""
    C, Hp, hd = q.shape
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    assert H % Hkv == 0, "chunked prefill needs whole GQA groups"
    k = lax.dynamic_index_in_dim(cache["k"], slot, 0, keepdims=False)
    v = lax.dynamic_index_in_dim(cache["v"], slot, 0, keepdims=False)
    slot_pos = lax.dynamic_index_in_dim(cache["slot_pos"], slot, 0,
                                        keepdims=False)   # (cap_loc,)
    qg = (q[:, :H].astype(jnp.float32) * hd ** -0.5).reshape(
        C, Hkv, H // Hkv, hd)
    s = jnp.einsum("cngd,knd->cngk", qg, k.astype(jnp.float32))
    qpos = start + jnp.arange(C)
    valid = jnp.logical_and(slot_pos[None, :] >= 0,
                            slot_pos[None, :] <= qpos[:, None])  # (C, cap_loc)
    o = _masked_softmax_attend(s, valid[:, None, None, :],
                               v.astype(jnp.float32), "cngk,knd->cngd", ctx)
    return jnp.pad(o.reshape(C, H, hd), ((0, 0), (0, Hp - H), (0, 0)))


def mixed_attention(p, x, cache, pos, chunk, cfg, ctx: ParallelCtx):
    """One decode step for B slots plus one prompt chunk of C tokens for
    one slot, every projection over the B + C rows together.

    x: (B + C, 1, D), the decode rows first; ``pos`` (B,): each decode
    row's position, negative for a slot that is not decoding (it writes
    nothing); ``chunk = (slot, start, n)``: the chunk's slot, the position
    of its first token and how many of its C rows are prompt tokens (the
    rest are padding and write nothing).  Returns (y (B + C, 1, D),
    cache')."""
    slot, start, n = chunk
    B = pos.shape[0]
    C = x.shape[0] - B
    pos_b = jnp.asarray(pos, jnp.int32)
    qpos = jnp.concatenate([pos_b, start + jnp.arange(C, dtype=jnp.int32)])
    q, k_new, v_new = _decode_qkv(p, x.reshape(B + C, -1), qpos, cfg, ctx)
    cache = _ring_write(cache, k_new[:B], v_new[:B], pos_b, ctx,
                        writes=pos_b >= 0)
    cache = _chunk_write(cache, k_new[B:, 0], v_new[B:, 0], slot, start, n,
                         ctx)
    o = jnp.concatenate([_decode_rows_attend(q[:B], cache, pos_b, cfg, ctx),
                         _chunk_attend(q[B:], cache, slot, start, cfg, ctx)])
    return _out_proj(p, o, x.dtype, cfg, ctx), cache


def kv_idx_full(cfg, Hp: int):
    g = max(cfg.n_heads // cfg.n_kv_heads, 1)
    gh = jnp.arange(Hp)
    return jnp.clip(gh // g, 0, cfg.n_kv_heads - 1)


def mask_full(cfg, Hp: int):
    return (jnp.arange(Hp) < cfg.n_heads).astype(jnp.float32)
