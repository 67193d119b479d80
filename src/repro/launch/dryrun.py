import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape) cell, lower + compile the production
step (train_step / serve_step / prefill) on the single-pod (16, 16) mesh and
the multi-pod (2, 16, 16) mesh, print ``memory_analysis()`` (proves it fits)
and ``cost_analysis()`` (FLOPs/bytes for the roofline), and parse the
compiled HLO for per-device collective wire bytes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_results.jsonl
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import COMM_MODES, ARCHS, SHAPES, cells, get_arch
from ..data.inputs import input_specs
from .mesh import make_production_mesh
from .steps import TrainSettings, build_prefill, build_serve, build_train

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\]\S*\s+(all-gather|all-reduce|reduce-scatter"
    r"|all-to-all|collective-permute)"
)


def _shape_bytes(dt: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-device wire-byte estimate from compiled HLO.

    Conventions (documented in EXPERIMENTS.md): all-gather counts its result
    bytes, reduce-scatter / all-to-all / collective-permute count operand ≈
    result bytes, all-reduce counts 2x operand (ring RS+AG).  All are the
    O(P-1/P) ring wire cost per device."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.groups()
        b = _shape_bytes(dt, dims)
        if kind == "all-reduce":
            b *= 2
        out[kind] += b
    out["total"] = sum(out.values())
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             comm_mode: str = "smi", settings: TrainSettings | None = None,
             shared_gather: bool = False, ring_attn: bool = False,
             remat: str = "nothing", variant: str = "base",
             verbose: bool = True) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec: dict = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "comm_mode": comm_mode, "variant": variant, "ok": False,
    }
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec["skipped"] = "pure full-attention arch (DESIGN.md §4)"
        rec["ok"] = True
        return rec

    t0 = time.time()
    try:
        if shape.kind == "train":
            st = settings or TrainSettings(
                comm_mode=comm_mode, shared_gather=shared_gather,
                ring_attn=ring_attn, remat=remat,
            )
            art = build_train(cfg, mesh, shape, st)
            batch_structs = {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in art["input_specs"].items()
            }
            lowered = art["step"].lower(art["state_shape"], batch_structs)
        elif shape.kind == "prefill":
            art = build_prefill(cfg, mesh, shape, comm_mode=comm_mode,
                                shared_gather=shared_gather,
                                ring_attn=ring_attn)
            args = [art["params_shape"], art["input_specs"]["tokens"]]
            if "pixel_embeds" in art["input_specs"]:
                args.append(art["input_specs"]["pixel_embeds"])
            lowered = art["step"].lower(*args)
        else:  # decode
            art = build_serve(cfg, mesh, shape, comm_mode=comm_mode)
            lowered = art["step"].lower(
                art["params_shape"], art["cache_shape"],
                art["input_specs"]["token"], art["input_specs"]["pos"],
            )
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gb": round(mem.argument_size_in_bytes / 2**30, 3),
            "output_gb": round(mem.output_size_in_bytes / 2**30, 3),
            "temp_gb": round(mem.temp_size_in_bytes / 2**30, 3),
            "alias_gb": round(mem.alias_size_in_bytes / 2**30, 3),
        }
        cost = compiled.cost_analysis() or {}
        rec["cost"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        rec["collectives"] = collective_bytes(compiled.as_text())
        rec["ok"] = True
        if verbose:
            print(f"[dryrun] {arch} {shape_name} mesh={rec['mesh']} "
                  f"mode={comm_mode} OK lower={rec['lower_s']}s "
                  f"compile={rec['compile_s']}s mem(temp)="
                  f"{rec['memory']['temp_gb']}GB flops={rec['cost']['flops']:.3g} "
                  f"coll={rec['collectives']['total']:.3g}B", flush=True)
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[dryrun] {arch} {shape_name} mesh={rec['mesh']} FAILED: "
                  f"{rec['error']}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--comm-mode", default="smi", choices=list(COMM_MODES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from .cache import enable_compile_cache

    enable_compile_cache()
    todo = []
    for arch, shape_name, skip in cells():
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape_name != args.shape:
            continue
        todo.append((arch, shape_name))
    if not todo:
        print("nothing selected", file=sys.stderr)
        return 1

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    results = []
    for arch, shape_name in todo:
        for mp in meshes:
            rec = run_cell(arch, shape_name, multi_pod=mp,
                           comm_mode=args.comm_mode)
            results.append(rec)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")

    n_bad = sum(1 for r in results if not r["ok"])
    print(f"[dryrun] {len(results) - n_bad}/{len(results)} cells OK")
    return 0 if n_bad == 0 else 2


if __name__ == "__main__":
    sys.exit(main())
