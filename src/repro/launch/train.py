"""Production training driver.

Wires together: arch config -> mesh -> SMI train step -> synthetic data
pipeline -> checkpointing -> watchdog + checkpoint/restart.  CLI:

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \\
        --steps 50 --comm-mode smi

``--smoke`` scales the arch to its reduced config so the driver runs on the
host devices; the full configs are exercised via the dry-run.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer
from ..configs import COMM_MODES, SHAPES, get_arch, smoke
from ..configs.base import ShapeConfig
from ..data.pipeline import SyntheticTokenPipeline
from ..ft import StepWatchdog
from .cache import enable_compile_cache
from .mesh import grid_for, make_mesh
from .steps import TrainSettings, build_train


def train_loop(
    cfg, mesh, shape, settings: TrainSettings, *,
    steps: int, ckpt_dir: str | None = None, ckpt_every: int = 50,
    log_every: int = 10, seed: int = 0, state=None, start_step: int = 0,
    fail_at: int | None = None,
):
    art = build_train(cfg, mesh, shape, settings)
    if state is None:
        state = art["init_state"](seed)
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    pipe = SyntheticTokenPipeline(
        cfg.vocab_size, shape.seq_len, shape.global_batch,
        seed=seed, n_codebooks=cfg.n_codebooks,
    )
    wd = StepWatchdog()
    wd.start()
    history = []
    try:
        for step in range(start_step, steps):
            if fail_at is not None and step == fail_at:
                raise RuntimeError("injected node failure")
            hostb = pipe.next()
            batch = {
                "tokens": jnp.asarray(hostb["tokens"]),
                "labels": jnp.asarray(hostb["labels"]),
            }
            if cfg.frontend == "vit_stub":
                rng = np.random.RandomState(seed * 7919 + step)
                batch["pixel_embeds"] = jnp.asarray(
                    rng.randn(shape.global_batch, cfg.n_patches, cfg.d_model)
                    * 0.02,
                    jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
                )
            state, metrics = art["step"](state, batch)
            slow = wd.lap(step)
            if step % log_every == 0 or step == steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                m.update(step=step, straggler=slow)
                history.append(m)
                print(f"[train] step={step} loss={m['loss']:.4f} "
                      f"ce={m['ce']:.4f} gnorm={m['gnorm']:.3f} lr={m['lr']:.2e}",
                      flush=True)
            if ckpt and step > 0 and step % ckpt_every == 0:
                ckpt.save(state, step, async_=True)
        if ckpt:
            ckpt.save(state, steps)
            ckpt.wait()
    finally:
        pipe.close()
    return state, history


def validate_comm(cfg, mesh, dims, shape, settings: TrainSettings) -> int:
    """Predicted-vs-measured channel traffic gate (DESIGN.md §12).

    Traces one training step (abstract lowering — no device compute),
    captures every tagged channel's ledger tallies, and diffs them against
    :func:`repro.netsim.predict_train_step_stats` per tag.  The contract is
    byte-exact: any per-tag difference in steps or bytes is a failure."""
    from ..netsim import predict_train_step_stats
    from ..parallel import ledger

    dp = int(np.prod(dims[:-1]))
    tp = dims[-1]
    art = build_train(cfg, mesh, shape, settings)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in art["input_specs"].items()}
    with ledger.capture() as led:
        art["step"].lower(art["state_shape"], batch)
    measured = {t: dict(e) for t, e in led.by_tag.items()}
    predicted = predict_train_step_stats(cfg, (dp, tp), shape, settings)

    mesh_s = ",".join(str(d) for d in dims)
    print(f"[validate-comm] arch={cfg.name} mesh={mesh_s} "
          f"comm={settings.comm_mode}")
    print(f"  {'tag':<16} {'pred bytes':>12} {'meas bytes':>12} "
          f"{'pred steps':>11} {'meas steps':>11}")
    failures = 0
    for tag in sorted(set(predicted) | set(measured)):
        p = predicted.get(tag, {"steps": 0, "bytes": 0})
        m = measured.get(tag, {"steps": 0, "bytes": 0})
        ok = p == m
        failures += 0 if ok else 1
        print(f"  {tag:<16} {p['bytes']:>12} {m['bytes']:>12} "
              f"{p['steps']:>11} {m['steps']:>11}  {'ok' if ok else 'FAIL'}")
    if failures:
        print(f"[validate-comm] FAIL: {failures} tag(s) diverge")
        return 1
    print(f"[validate-comm] ok: {len(measured)} tags byte-exact "
          f"({sum(e['bytes'] for e in measured.values())} bytes/step)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (host-scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="data,model grid (default: the squarest grid of "
                         "the devices present)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--comm-mode", default="smi", choices=list(COMM_MODES),
                    help="collective mode; smi:<backend> picks the "
                         "transport (see repro/transport)")
    ap.add_argument("--remat", default="nothing")
    ap.add_argument("--compressed-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--validate-comm", action="store_true",
                    help="trace one step and gate the per-tag channel "
                         "ledger against netsim's prediction, byte-exact")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    dims = (tuple(int(x) for x in args.mesh.split(",")) if args.mesh
            else grid_for(jax.device_count()))
    mesh = make_mesh(dims, ("data", "model")[: len(dims)] if len(dims) == 2
                     else ("pod", "data", "model"))
    shape = ShapeConfig("cli", seq_len=args.seq_len, global_batch=args.batch,
                        kind="train")
    st = TrainSettings(
        comm_mode=args.comm_mode, remat=args.remat, base_lr=args.lr,
        loss_chunks=1 if args.smoke else 8,
        compressed_grads=args.compressed_grads,
        total_steps=max(args.steps, 10),
        warmup_steps=max(args.steps // 10, 1),
    )
    if args.validate_comm:
        return validate_comm(cfg, mesh, dims, shape, st)
    t0 = time.time()
    _, history = train_loop(
        cfg, mesh, shape, st, steps=args.steps, ckpt_dir=args.ckpt_dir
    )
    print(f"[train] done in {time.time() - t0:.1f}s; "
          f"first loss {history[0]['loss']:.4f} -> last {history[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
