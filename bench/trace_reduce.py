"""Reduce a profiler trace to what the per-layer metrics read.

A traced run writes one ``.xplane.pb`` (``jax.profiler.start_trace``).  This
module reads it with ``jax.profiler.ProfileData`` and keeps three things:

* for each device, the operations that ran on it (name, start, end);
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
  that start with ``bench.``), on the same clock;
* the traced window: the ``bench.window`` span the harness opens around
  the measured loop.

Everything else about a trace (its planes, lines and metadata) stays here,
so that a metric's reader deals only in intervals and names.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

#: host spans of the benchmark's own; the harness names its window so
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

#: device planes are named "/device:TPU:<n>"; the line that holds one
#: event per executed HLO operation is "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_OPS_LINE = "XLA Ops"

#: operations that move data between chips, matched on the instruction's
#: name.  Asynchronous ones appear as a start and a done event; the done
#: event lasts as long as the device waits for the transfer
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv)")


@dataclass
class Op:
    name: str            # the HLO instruction's name, e.g. "fusion.154"
    start: float         # ns
    end: float           # ns
    custom: bool = False  # a custom call: a Pallas kernel on the TPU
    leaf: bool = True    # runs no other op of its line inside it (a while
    #                      loop or a call holds its body's ops)


def short_name(text: str) -> str:
    """A device op event is named by its HLO text, ``%name = type op(...)``;
    keep the instruction's name."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _mark_containers(ops: list[Op]) -> None:
    stack: list[Op] = []
    for op in sorted(ops, key=lambda o: (o.start, -(o.end - o.start))):
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            stack[-1].leaf = False
        stack.append(op)


@dataclass
class Device:
    index: int
    ops: list[Op] = field(default_factory=list)


@dataclass
class Reduced:
    devices: list[Device]
    spans: list[Op]
    window: tuple[float, float]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged) -> float:
    return sum(b - a for a, b in merged)


def subtract(merged_a, merged_b) -> float:
    """Length of the union ``merged_a`` not covered by the union
    ``merged_b`` (both as :func:`union` returns them)."""
    total, j = 0.0, 0
    for s, e in merged_a:
        while j < len(merged_b) and merged_b[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(merged_b) and merged_b[k][0] < e:
            covered += min(e, merged_b[k][1]) - max(s, merged_b[k][0])
            k += 1
        total += (e - s) - covered
    return total


def busy_ns(dev: Device, window) -> float:
    """Time in ``window`` during which some operation ran on ``dev``."""
    return length(union(((o.start, o.end) for o in dev.ops), *window))


def idle_share(red: Reduced) -> float:
    """1 - busy / window, with busy averaged over the devices."""
    busy = sum(busy_ns(d, red.window) for d in red.devices) / len(red.devices)
    return 1.0 - busy / red.window_ns


def exposed_ns(dev: Device, window) -> float:
    """Time in ``window`` in which a collective runs on ``dev`` and no
    other op does (a loop that holds it does not count)."""
    hit = union(((o.start, o.end) for o in dev.ops if COLLECTIVE.search(o.name)),
                *window)
    rest = union(((o.start, o.end) for o in dev.ops
                  if o.leaf and not COLLECTIVE.search(o.name)), *window)
    return subtract(hit, rest)


def reduce(profile) -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``.  The window is the
    first ``WINDOW_SPAN`` host span; a trace without one is refused."""
    devices: dict[int, Device] = {}
    spans: list[Op] = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == _OPS_LINE:
                dev = devices.setdefault(int(m.group(1)), Device(int(m.group(1))))
                for e in line.events:
                    text = e.name
                    dev.ops.append(Op(short_name(text), e.start_ns, e.end_ns,
                                      "custom_call_target" in text))
            elif not m:
                spans.extend(Op(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for dev in devices.values():
        _mark_containers(dev.ops)
    wins = [s for s in spans if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("trace has no device operations")
    return Reduced([devices[k] for k in sorted(devices)], spans,
                   (wins[0].start, wins[0].end))


def load(trace_dir) -> Reduced:
    """Reduce the one ``.xplane.pb`` written under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return reduce(ProfileData.from_file(str(files[0])))


def top_ops(red: Reduced, n: int = 10) -> list[list]:
    """The ``n`` operation names (numbering dropped) that took most device
    time in the window, with seconds summed over devices and divided by
    their number.  Ops that hold others (loops) are left out."""
    tot: dict[str, float] = {}
    lo, hi = red.window
    for d in red.devices:
        for o in d.ops:
            dt = min(o.end, hi) - max(o.start, lo)
            if dt > 0 and o.leaf:
                key = re.sub(r"\.\d+$", "", o.name)
                tot[key] = tot.get(key, 0.0) + dt
    nd = len(red.devices)
    return [[k, v / nd * 1e-9]
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(red: Reduced, n: int = 10) -> list[list]:
    """The ``n`` longest gaps in which device 0 ran nothing, each named by
    the benchmark's innermost host span that covers the gap's middle (or
    ``"outside spans"``), with its length in seconds."""
    dev = red.devices[0]
    busy = union(((o.start, o.end) for o in dev.ops), *red.window)
    gaps, cur = [], red.window[0]
    for a, b in busy + [(red.window[1], red.window[1])]:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = 0.5 * (a + b)
        cover = [s for s in red.spans if s.start <= mid <= s.end
                 and s.name != WINDOW_SPAN]
        name = min(cover, key=lambda s: s.end - s.start).name if cover \
            else "outside spans"
        out.append([name, (b - a) * 1e-9])
    return out
