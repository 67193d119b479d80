"""The harness: finds a cell's files by name, runs its driver, prints the line.

A run is: set-up (the driver builds the program, makes its inputs from the
seed and warms up every shape the window uses), one measured window, the
memory peak, then the driver frees the program's state and checks what the
window produced against the plain reference.  With ``--trace 1`` the
profiler records the last ``trace_seconds`` of the window and the cell's
per-layer metrics are read from the reduced trace; otherwise the metrics are
the cell's end-to-end ones.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

from bench import trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A run that cannot produce a result: no chip, no program, a cell or a
    file that does not exist."""


# ------------------------------------------------------------------ the cell


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def load_cell(name: str, root: Path = ROOT, spec: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``spec``, laid out
    alike), with its configuration file, its traffic file and the metrics
    that list it.  An end-to-end metric without a ``workloads`` key is every
    cell's; a per-layer metric has to list its cells."""
    spec = spec or _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    bare = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    if bare:
        raise BenchError(f"per-layer metrics {bare} list no workloads")
    layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


# ------------------------------------------------------------ the window


class Window:
    """The measured window, driven by the driver's loop::

        win.start()
        while win.open:
            win.tick()
            ...one iteration (a tick, a dispatch, a step)...
        win.stop()

    ``tick`` is called once before each iteration, which numbers the
    iterations.  With a trace directory, the profiler records the
    iterations that start in the last ``trace_seconds`` of the window
    (``traced`` gives their range), inside a ``bench.window`` span, until
    ``end_trace`` or ``stop``.  ``tick(sync)`` calls ``sync()`` before it
    starts the profiler, so that work enqueued before the trace has
    finished when it begins.
    """

    def __init__(self, seconds: float, trace_dir: str | None = None,
                 trace_seconds: float | None = None):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.trace_seconds = min(self.seconds, float(trace_seconds or seconds))
        self.t0 = self.t1 = None
        self.iterations = 0
        self.traced = None  # (first, end) iteration indices inside the trace
        self._span = None

    def start(self) -> None:
        self.t0 = time.perf_counter()

    @property
    def now(self) -> float:
        """Seconds since the window opened."""
        return time.perf_counter() - self.t0

    @property
    def open(self) -> bool:
        return self.now < self.seconds

    def tick(self, sync=None) -> None:
        if (self.trace_dir and self.traced is None
                and self.now >= self.seconds - self.trace_seconds):
            if sync is not None:
                sync()
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.traced = (self.iterations, None)
        self.iterations += 1

    def end_trace(self) -> None:
        """Stop the profiler, if it runs, after the last traced iteration's
        results are on the host."""
        if self._span is not None:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.traced = (self.traced[0], self.iterations)
            self._span = None

    def stop(self) -> None:
        """Call once the last iteration's results are on the host."""
        self.t1 = time.perf_counter()
        self.end_trace()

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0


# ------------------------------------------------------------- a run's parts


@dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit   # NaN compares false


@dataclass
class Measured:
    """What a driver's window gives: end-to-end values by metric name, the
    work attempted and failed, and whatever its per-layer readers read."""
    metrics: dict[str, float]
    attempted: int
    failed: int
    stats: dict = field(default_factory=dict)


@dataclass
class LayerContext:
    """What a per-layer metric's reader gets."""
    cell: Cell
    peaks: dict
    stats: dict
    trace: object  # trace_reduce.Reduced
    chips: int


def read_layer_metric(name: str, ctx: LayerContext):
    """Run ``layer_metrics/<name>.py``'s ``read``; None where it finds
    nothing to read."""
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_layer_{name}", path)
    if spec is None or not path.exists():
        raise BenchError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def load_driver(cell: Cell):
    return importlib.import_module(f"bench.drivers.{cell.driver}")


# ------------------------------------------------------------------ devices


def accelerator(chips: int):
    """The chips this run uses, and their kind's peaks; raises where JAX
    finds no TPU, fewer chips than the cell asks for, or a kind that the
    peaks table does not hold."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = _load_json(BENCH / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], peaks[kind]


def use_program(root: Path = ROOT) -> None:
    """Put the program under test (``<checkout>/src``) on the path and keep
    JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    import os

    import jax

    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no program under test at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ------------------------------------------------------------------- a run


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float, driver=None):
    """Set up, measure, check; returns the result line as a dict, and the
    driver (which still holds what its check read).
    ``driver`` replaces the cell's driver class (a control, a fault)."""
    import jax

    drv = (driver or load_driver(cell).Driver)(cell, seed=seed, devices=devices,
                                               seconds=seconds)
    drv.setup()
    setup_s = time.perf_counter() - t_start

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        win = Window(seconds, tdir if trace else None,
                     cell.traffic.get("trace_seconds"))
        measured = drv.measure(win)
        mem = memory_peak(devices)
        red = trace_reduce.load(tdir) if trace else None
    drv.release()
    gc.collect()
    jax.clear_caches()   # unload the program's executables before the check
    checks = drv.check()

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    line = {"correct": bool(checks) and all(c.ok for c in checks)
            and measured.failed == 0,
            "attempted": measured.attempted, "failed": measured.failed}
    if trace:
        ctx = LayerContext(cell, peaks, measured.stats, red, len(devices))
        metrics = {}
        for m in cell.per_layer:
            v = read_layer_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = sum(trace_reduce.busy_ns(d, red.window) for d in red.devices)
        device["busy_s"] = busy / len(red.devices) * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(red),
                             "idle_gaps": trace_reduce.idle_gaps(red)}
    else:
        values = dict(measured.metrics, setup_s=setup_s)
        missing = [m["name"] for m in cell.end_to_end if m["name"] not in values]
        if missing:
            raise BenchError(f"driver {cell.driver} gave no {missing}")
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line, drv


def parse_args(argv):
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
        use_program()
        devices, peaks = accelerator(cell.chips)
        line, _ = run_cell(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), devices=devices, peaks=peaks,
                        t_start=t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
