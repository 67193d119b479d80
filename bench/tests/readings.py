"""Readings of a cell's compared numbers, for setting its limits on the chip.

    python3 -m bench.tests.readings --workload <cell> --seeds 1 2 3 \\
        [--seconds 5] [--traffic '{"rate_per_s": 10}'] [--control 1] \\
        [--config '{"vocab_size": 16128}'] [--held-back 1] \\
        [--driver bench.tests.test_correctness:TrainHalfBatch]

One process runs the cell once per seed (set-up, window, check), as the
benchmark does, and prints one JSON line per seed: the program's reading of
each compared number, with ``--control 1`` the control's reading (the
reference in the next lower precision in the program's place, from the
driver's ``control``), and what the window measured.  ``--driver`` reads
a planted fault in the program's place instead.  ``--held-back 1`` also
finds the cells of ``bench/tests/held_back.json``: cells held out of
``BENCHMARK.json`` because the program fails them (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def held_back_spec() -> dict:
    """``BENCHMARK.json`` with the held-back cells and their metrics added."""
    from bench import harness

    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    extra = json.loads((Path(__file__).parent / "held_back.json").read_text())
    for key, entries in extra.items():
        spec[key] = spec[key] + entries
    return spec


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--traffic", default="{}")
    ap.add_argument("--config", default="{}")
    ap.add_argument("--held-back", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--driver", default=None,
                    help="module:Class in place of the cell's driver, such "
                         "as a fault of bench/tests/test_correctness.py")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload,
                             spec=held_back_spec() if args.held_back else None)
    cell.traffic.update(json.loads(args.traffic))
    cell.config.update(json.loads(args.config))
    harness.use_program()
    devices, peaks = harness.accelerator(cell.chips)
    mod = harness.load_driver(cell)
    driver = None
    if args.driver:
        import importlib

        name, _, cls = args.driver.partition(":")
        driver = getattr(importlib.import_module(name), cls)
    for seed in args.seeds:
        t0 = time.perf_counter()
        line, drv = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                     trace=bool(args.trace), devices=devices,
                                     peaks=peaks, t_start=t0, driver=driver)
        out = {"seed": seed, "correct": line["correct"],
               "checks": line["checks"], "metrics": line["metrics"],
               "attempted": line["attempted"], "failed": line["failed"],
               "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
        if args.control:
            out["control"] = mod.control(drv)
        summary = getattr(mod, "summary", None)
        if summary is not None:
            out["summary"] = summary(drv)
        out["run_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
