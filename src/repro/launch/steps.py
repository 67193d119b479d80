"""Step builders: train_step / serve_step / prefill_step for (cfg, mesh).

These are the production entry points shared by the trainer, the serving
engine, the dry-run, and the roofline analysis.  Everything distributed is
explicit: the model runs inside one shard_map over the full mesh with SMI
(or bulk) collectives; the optimizer runs at the jit level where the
FSDP/ZeRO layouts are pure sharding annotations.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..data.inputs import input_specs
from ..mesh.api import (
    build_fsdp_plan,
    fsdp_storage_specs,
    grad_sync_fsdp,
    make_ctx,
)
from ..models import (
    init_lm,
    lm_cache_specs,
    lm_caches,
    lm_decode_step,
    lm_mixed_step,
    lm_loss,
    lm_prefill,
    lm_specs,
)
from ..optim import adamw_init, adamw_update, clip_by_global_norm, cosine_warmup
from .mesh import batch_axes_of


@dataclass
class TrainSettings:
    #: "smi" | "smi:static" | "smi:packet" | "smi:fused" | "bulk" — base
    #: collective mode plus transport backend (repro/transport registry)
    comm_mode: str = "smi"
    remat: str = "nothing"
    loss_chunks: int = 8
    base_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    clip_norm: float = 1.0
    fsdp: bool = True
    compressed_grads: bool = False
    shared_gather: bool = False   # beyond-paper §Perf optimisation
    ring_attn: bool = False       # beyond-paper §Perf optimisation


def globalize_structs(local_tree, spec_tree, mesh):
    """Per-device cache/struct shapes -> global shapes per the spec tree
    (multiply each sharded dim by its axis size)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(l, sp):
        dims = tuple(sp) + (None,) * (len(l.shape) - len(tuple(sp)))
        shape = []
        for d, sz in zip(dims, l.shape):
            mult = 1
            if d is not None:
                for a in (d if isinstance(d, tuple) else (d,)):
                    mult *= sizes[a]
            shape.append(sz * mult)
        return jax.ShapeDtypeStruct(tuple(shape), l.dtype)

    return jax.tree.map(
        one, local_tree, spec_tree,
        is_leaf=lambda x: hasattr(x, "shape"),
    )


def _sh(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _batch_spec(shape_leaf, batch_axes, mesh):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a in batch_axes:
        dp *= sizes[a]
    if shape_leaf.shape and shape_leaf.shape[0] % dp == 0 and shape_leaf.shape[0] > 0:
        ax = tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]
        return P(*((ax,) + (None,) * (len(shape_leaf.shape) - 1)))
    return P(*((None,) * len(shape_leaf.shape)))


def _layer_plan(cfg: ModelConfig, comm_mode: str):
    """The per-tag layer plan a launch selects: the config's ``comm_plan``
    (default "auto") when the comm_mode string doesn't pin a transport
    backend; an explicit ``smi:<backend>`` (or bulk/none) is the escape
    hatch and keeps layers on the pinned backend (plan None)."""
    return cfg.comm_plan if comm_mode == "smi" else None


def build_train(cfg: ModelConfig, mesh, shape: ShapeConfig, st: TrainSettings):
    """Returns dict with jitted ``step``, ``init_state``, shardings, specs."""
    batch_axes = batch_axes_of(mesh)
    ctx = make_ctx(mesh, model_axis="model", batch_axes=batch_axes,
                   comm_mode=st.comm_mode,
                   opt_shared_gather=st.shared_gather,
                   opt_ring_attn=st.ring_attn,
                   plan=_layer_plan(cfg, st.comm_mode))
    pspecs = lm_specs(cfg, ctx)
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda: init_lm(key, cfg, ctx))
    plan = build_fsdp_plan(pshapes, pspecs, mesh, batch_axes) if st.fsdp else None
    store_specs = fsdp_storage_specs(pspecs, plan, batch_axes) if st.fsdp else pspecs

    ispecs = input_specs(cfg, shape)
    bspecs = {k: _batch_spec(v, batch_axes, mesh) for k, v in ispecs.items()}
    has_pix = "pixel_embeds" in ispecs

    # ---- loss + synced grads, explicit-SPMD region
    def loss_grads(params, tokens, labels, *extra):
        def lf(p):
            loss, (ce, aux) = lm_loss(
                p, tokens, labels, cfg, ctx,
                extra_embeds=extra[0] if extra else None,
                remat=st.remat, loss_chunks=st.loss_chunks, fsdp_plan=plan,
            )
            return loss, (ce, aux)

        (loss, (ce, aux)), grads = jax.value_and_grad(lf, has_aux=True)(params)
        grads = grad_sync_fsdp(grads, plan, ctx, compressed=st.compressed_grads) \
            if plan is not None else grads
        if plan is None and batch_axes:
            grads = jax.tree.map(lambda g: lax.pmean(g, batch_axes), grads)
        loss_s = lax.pmean(loss, batch_axes) if batch_axes else loss
        ce_s = lax.pmean(ce, batch_axes) if batch_axes else ce
        return loss_s, ce_s, grads

    in_specs = (store_specs, bspecs["tokens"], bspecs["labels"])
    if has_pix:
        in_specs = in_specs + (bspecs["pixel_embeds"],)
    smapped = jax.shard_map(
        loss_grads, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P(), store_specs),
        check_vma=False,
    )

    state_specs = {
        "params": store_specs,
        "opt": {"m": store_specs, "v": store_specs, "step": P()},
    }
    state_sh = _sh(mesh, state_specs)
    batch_sh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}

    def step_fn(state, batch):
        args = (state["params"], batch["tokens"], batch["labels"])
        if has_pix:
            args = args + (batch["pixel_embeds"],)
        loss, ce, grads = smapped(*args)
        grads, gnorm = clip_by_global_norm(grads, st.clip_norm)
        lr = cosine_warmup(
            state["opt"]["step"], base_lr=st.base_lr,
            warmup_steps=st.warmup_steps, total_steps=st.total_steps,
        )
        new_p, new_opt = adamw_update(state["params"], grads, state["opt"], lr=lr)
        return (
            {"params": new_p, "opt": new_opt},
            {"loss": loss, "ce": ce, "gnorm": gnorm, "lr": lr},
        )

    step = jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )

    def init_state(seed=0):
        k = jax.random.PRNGKey(seed)
        params = init_lm(k, cfg, ctx)
        return {"params": params, "opt": adamw_init(params)}

    init_jit = jax.jit(init_state, static_argnums=(0,), out_shardings=state_sh)

    state_shape = jax.eval_shape(init_state)
    return dict(
        step=step, init_state=init_jit, state_shape=state_shape,
        state_sharding=state_sh, batch_sharding=batch_sh, ctx=ctx,
        input_specs=ispecs, plan=plan, store_specs=store_specs,
    )


def build_serve(cfg: ModelConfig, mesh, shape: ShapeConfig, *,
                comm_mode: str = "smi", fsdp: str | bool = "auto"):
    """serve_step: one token for the whole batch against a full KV cache."""
    batch_axes = batch_axes_of(mesh)
    ctx = make_ctx(mesh, model_axis="model", batch_axes=batch_axes,
                   comm_mode=comm_mode, plan=_layer_plan(cfg, comm_mode))
    pspecs = lm_specs(cfg, ctx)
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda: init_lm(key, cfg, ctx))

    if fsdp == "auto":
        # weight-stream (ZeRO-3-style gather per layer) only when a pure
        # TP shard would not fit HBM (bf16 params/device > 10 GB)
        total = sum(
            int(jnp.prod(jnp.asarray(l.shape))) for l in jax.tree.leaves(pshapes)
        )
        fsdp = (total / ctx.tp) * 2 > 10e9
    plan = build_fsdp_plan(pshapes, pspecs, mesh, batch_axes) if fsdp else None
    store_specs = fsdp_storage_specs(pspecs, plan, batch_axes) if fsdp else pspecs

    ispecs = input_specs(cfg, shape)
    bspec_tok = _batch_spec(ispecs["token"], batch_axes, mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a in batch_axes:
        dp *= sizes[a]
    shard_batch = shape.global_batch % dp == 0 and dp > 1
    cspecs = lm_cache_specs(cfg, ctx, shard_batch=shard_batch)
    B_loc = shape.global_batch // dp if shard_batch else shape.global_batch

    def serve_step(params, caches, token, pos):
        logits, caches = lm_decode_step(
            params, caches, token, pos, cfg, ctx,
            gather_logits=False, fsdp_plan=plan,
        )
        return logits, caches

    b0 = bspec_tok[0] if len(tuple(bspec_tok)) else None
    logit_spec = (
        P(b0, "model", None) if cfg.n_codebooks > 1 else P(b0, "model")
    )
    smapped = jax.shard_map(
        serve_step, mesh=mesh,
        in_specs=(store_specs, cspecs, bspec_tok, P()),
        out_specs=(logit_spec, cspecs),
        check_vma=False,
    )
    cache_sh = _sh(mesh, cspecs)
    param_sh = _sh(mesh, store_specs)

    step = jax.jit(
        smapped,
        in_shardings=(param_sh, cache_sh, NamedSharding(mesh, bspec_tok), None),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
    )

    capacity = shape.seq_len
    cache_local = jax.eval_shape(
        lambda: lm_caches(cfg, B_loc, capacity=capacity, ctx=ctx)
    )
    cache_shape = globalize_structs(cache_local, cspecs, mesh)

    def params_shape_bf16():
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype
            ),
            pshapes,
        )

    return dict(
        step=step, ctx=ctx, cache_shape=cache_shape,
        params_shape=params_shape_bf16(), param_sharding=param_sh,
        cache_sharding=cache_sh, input_specs=ispecs, B_loc=B_loc,
        capacity=capacity, store_specs=store_specs, plan=plan,
    )


def build_continuous_serve(cfg: ModelConfig, mesh, *, comm_mode: str = "smi",
                           batch_slots: int = 4, capacity: int = 128,
                           fsdp: str | bool = "auto"):
    """Tensor-parallel runtime for the continuous-batching engine.

    Returns the ``runtime`` dict :class:`~repro.serving.ContinuousEngine`
    consumes: the shard_map'd per-slot decode step (``pos`` is a (B,)
    vector), the same step carrying one prompt chunk (``mixed_step``,
    :func:`~repro.models.lm_mixed_step`), the slot-invalidation step, the two migration legs on the
    pool's ``serve.migrate`` gather/scatter channels, and the
    :class:`~repro.channels.ChannelPool` whose persistent port claims
    outlive every trace (released only by ``pool.close()`` / engine
    shutdown).  Every layer channel inside the step resolves to ONE
    persistent pool spec per tag, reused across all decode steps.

    Slots are batch rows replicated over the data axes (slot scheduling
    is a global decision); the KV cache stays sequence-sharded over the
    model axis, which is what migration streams across ranks.
    """
    import dataclasses as _dc

    from ..channels import ChannelPool
    from ..serving.continuous import (
        migrate_gather,
        migrate_scatter,
        open_migration,
        serve_reset_slot,
    )

    ctx = make_ctx(mesh, model_axis="model", batch_axes=(),
                   comm_mode=comm_mode, plan=_layer_plan(cfg, comm_mode))
    pool = gspec = sspec = None
    if ctx.is_smi and ctx.model_comm is not None:
        pool = ChannelPool(ctx.model_comm, prefix="serve.")
        ctx = _dc.replace(ctx, channels=pool)
        gspec, sspec = open_migration(pool)
    pspecs = lm_specs(cfg, ctx)
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda: init_lm(key, cfg, ctx))
    if fsdp == "auto":
        total = sum(
            int(jnp.prod(jnp.asarray(l.shape))) for l in jax.tree.leaves(pshapes)
        )
        fsdp = (total / ctx.tp) * 2 > 10e9
    batch_axes = batch_axes_of(mesh)
    plan = build_fsdp_plan(pshapes, pspecs, mesh, batch_axes) if fsdp else None
    store_specs = fsdp_storage_specs(pspecs, plan, batch_axes) if fsdp else pspecs
    cspecs = lm_cache_specs(cfg, ctx, shard_batch=False)

    def serve_decode_step(params, caches, token, pos):
        return lm_decode_step(params, caches, token, pos, cfg, ctx,
                              gather_logits=False, fsdp_plan=plan)

    def serve_mixed_step(params, caches, token, pos, chunk_tok, chunk_slot,
                         chunk_start, chunk_len):
        return lm_mixed_step(params, caches, token, pos, chunk_tok,
                             chunk_slot, chunk_start, chunk_len, cfg, ctx,
                             gather_logits=False, fsdp_plan=plan)

    tok_spec = P(None, None) if cfg.n_codebooks > 1 else P(None)
    logit_spec = (
        P(None, "model", None) if cfg.n_codebooks > 1 else P(None, "model")
    )
    param_sh = _sh(mesh, store_specs)
    cache_sh = _sh(mesh, cspecs)
    step = jax.jit(
        jax.shard_map(
            serve_decode_step, mesh=mesh,
            in_specs=(store_specs, cspecs, tok_spec, P(None)),
            out_specs=(logit_spec, cspecs), check_vma=False,
        ),
        in_shardings=(param_sh, cache_sh, None, None),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
    )
    # the decode step plus one replicated prompt chunk (dense configs)
    mixed_step = jax.jit(
        jax.shard_map(
            serve_mixed_step, mesh=mesh,
            in_specs=(store_specs, cspecs, tok_spec, P(None), P(None), P(),
                      P(), P()),
            out_specs=(logit_spec, P("model"), cspecs), check_vma=False,
        ),
        in_shardings=(param_sh, cache_sh, None, None, None, None, None, None),
        out_shardings=(None, None, cache_sh),
        donate_argnums=(1,),
    )

    reset = jax.jit(
        jax.shard_map(serve_reset_slot, mesh=mesh, in_specs=(cspecs, P()),
                      out_specs=cspecs, check_vma=False),
        in_shardings=(cache_sh, None), out_shardings=cache_sh,
        donate_argnums=(0,),
    )

    # migration legs: gather every rank's packed slot image to the root,
    # later scatter it back out into the destination slot.  The in-flight
    # handle is the per-rank (P, N) gather result, stacked over the model
    # axis — opaque to the engine.
    if gspec is not None:
        def mig_start(caches, slot):
            return migrate_gather(caches, slot, gspec)

        def mig_finish(caches, inflight, slot):
            return migrate_scatter(caches, inflight, slot, sspec)
    else:
        # bulk / non-SMI: no channels — the image round-trips locally
        from ..serving.continuous import pack_slot, unpack_slot

        def mig_start(caches, slot):
            return pack_slot(caches, slot)[None]

        def mig_finish(caches, inflight, slot):
            return unpack_slot(caches, inflight[0], slot)

    migrate_start = jax.jit(
        jax.shard_map(mig_start, mesh=mesh, in_specs=(cspecs, P()),
                      out_specs=P("model", None), check_vma=False),
        in_shardings=(cache_sh, None),
    )
    migrate_finish = jax.jit(
        jax.shard_map(mig_finish, mesh=mesh,
                      in_specs=(cspecs, P("model", None), P()),
                      out_specs=cspecs, check_vma=False),
        in_shardings=(cache_sh, None, None), out_shardings=cache_sh,
        donate_argnums=(0,),
    )

    init_caches = jax.jit(
        jax.shard_map(
            lambda: lm_caches(cfg, batch_slots, capacity=capacity, ctx=ctx),
            mesh=mesh, in_specs=(), out_specs=cspecs, check_vma=False,
        ),
        out_shardings=cache_sh,
    )

    return dict(
        ctx=ctx, pool=pool, step=step, mixed_step=mixed_step, reset=reset,
        migrate_start=migrate_start, migrate_finish=migrate_finish,
        init_caches=init_caches, batch_slots=batch_slots, capacity=capacity,
        param_sharding=param_sh, cache_sharding=cache_sh,
        store_specs=store_specs, plan=plan,
    )


def build_prefill(cfg: ModelConfig, mesh, shape: ShapeConfig, *,
                  comm_mode: str = "smi", fsdp: str | bool = "auto",
                  shared_gather: bool = False, ring_attn: bool = False):
    batch_axes = batch_axes_of(mesh)
    ctx = make_ctx(mesh, model_axis="model", batch_axes=batch_axes,
                   comm_mode=comm_mode, opt_shared_gather=shared_gather,
                   opt_ring_attn=ring_attn,
                   plan=_layer_plan(cfg, comm_mode))
    pspecs = lm_specs(cfg, ctx)
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda: init_lm(key, cfg, ctx))
    if fsdp == "auto":
        total = sum(
            int(jnp.prod(jnp.asarray(l.shape))) for l in jax.tree.leaves(pshapes)
        )
        fsdp = (total / ctx.tp) * 2 > 10e9
    plan = build_fsdp_plan(pshapes, pspecs, mesh, batch_axes) if fsdp else None
    store_specs = fsdp_storage_specs(pspecs, plan, batch_axes) if fsdp else pspecs

    ispecs = input_specs(cfg, shape)
    bspecs = {k: _batch_spec(v, batch_axes, mesh) for k, v in ispecs.items()}
    has_pix = "pixel_embeds" in ispecs

    def prefill(params, tokens, *extra):
        h = lm_prefill(
            params, tokens, cfg, ctx, capacity=shape.seq_len,
            extra_embeds=extra[0] if extra else None, fsdp_plan=plan,
        )
        return h

    in_specs = (store_specs, bspecs["tokens"])
    if has_pix:
        in_specs = in_specs + (bspecs["pixel_embeds"],)
    bspec_tok = bspecs["tokens"]
    out_spec = P(bspec_tok[0] if bspec_tok else None, "model", None)
    smapped = jax.shard_map(
        prefill, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
        check_vma=False,
    )
    param_sh = _sh(mesh, store_specs)
    step = jax.jit(
        smapped,
        in_shardings=(param_sh,) + tuple(
            NamedSharding(mesh, bspecs[k]) for k in (["tokens", "pixel_embeds"] if has_pix else ["tokens"])
        ),
    )

    def params_shape_bf16():
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype
            ),
            pshapes,
        )

    return dict(
        step=step, ctx=ctx, params_shape=params_shape_bf16(),
        param_sharding=param_sh, input_specs=ispecs, store_specs=store_specs,
        plan=plan,
    )
