"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Runs on 8 host devices
(set in benchmarks/common.py before jax init); the production-mesh numbers
come from launch/dryrun.py + launch/roofline.py instead.

    PYTHONPATH=src python -m benchmarks.run [--only bandwidth,...]
                                            [--json out.json]
                                            [--validate-sim]

``--json`` writes every row machine-readably (suite, name, params,
us_per_call, derived) for BENCH_*.json perf-trajectory files (DESIGN.md
§6), plus a ``metrics`` snapshot of every transport the suites registered
with :mod:`repro.obs.metrics` (drift gauges included).  ``--validate-sim``
makes the suites that have a netsim prediction (latency, bandwidth,
injection) assert prediction-vs-measurement agreement within 2x — the
simulator/measurement drift gate CI runs.  ``--trace out.json`` records
channel/router/tuner events for the whole run and writes a Chrome-trace
file loadable in Perfetto (DESIGN.md §11).
"""

import argparse
import inspect
import json
import sys
import time
import traceback

from . import common  # noqa: F401  (sets XLA_FLAGS before jax init)

SUITES = [
    "bandwidth",        # Fig 9
    "latency",          # Tab 3
    "injection",        # Tab 4
    "collectives_bench",  # Fig 10 / Fig 11
    "gesummv",          # Fig 13
    "stencil_bench",    # Fig 15 / Fig 16
    "resources",        # Tab 1 / Tab 2
    "train_bench",      # channel-native train step (DESIGN.md §12)
    "serving_bench",    # continuous vs wave batching + serve.* channels
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suites")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write machine-readable results to OUT")
    ap.add_argument("--validate-sim", action="store_true",
                    help="assert netsim predictions within 2x of measurement")
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="record obs events and write a Chrome trace to OUT")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.enable(capacity=1 << 20)
    todo = args.only.split(",") if args.only else SUITES
    failures = []
    results = []
    for name in todo:
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        n0 = len(common.RESULTS)
        # every failure mode of one suite — import error, a raising run(),
        # even a stray sys.exit(0) inside a suite — must mark the suite
        # failed and continue, so a late failure can never be swallowed
        # (or the whole driver short-circuited to success) before the
        # summary: the CI perf gates downstream rely on this exit code
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            kwargs = {}
            if args.validate_sim and \
                    "validate_sim" in inspect.signature(mod.run).parameters:
                kwargs["validate_sim"] = True
            mod.run(**kwargs)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except KeyboardInterrupt:
            raise
        except BaseException as e:  # noqa: BLE001 — incl. SystemExit
            failures.append(name)
            traceback.print_exc()
            print(f"# {name} FAILED: {e}", flush=True)
        for row in common.RESULTS[n0:]:
            results.append({"suite": name, **row})
    if args.trace:
        from repro.obs import trace as obs_trace
        from repro.obs.export import write_chrome_trace
        tracer = obs_trace.disable()
        n_ev = write_chrome_trace(args.trace, tracer.events() if tracer else [])
        print(f"# wrote {n_ev} trace events to {args.trace}")
    if args.json:
        from repro.obs.metrics import REGISTRY
        # written before the exit-code decision: a red run still leaves
        # its partial rows on disk for the perf-trajectory diff
        with open(args.json, "w") as f:
            json.dump({
                "argv": sys.argv[1:],
                "validate_sim": args.validate_sim,
                "failures": failures,
                "rows": results,
                "metrics": REGISTRY.snapshot(),
            }, f, indent=1)
        print(f"# wrote {len(results)} rows to {args.json}")
    if failures:
        print(f"# FAILED suites: {failures}")
        sys.exit(1)
    print("# all benchmark suites completed")


if __name__ == "__main__":
    main()
