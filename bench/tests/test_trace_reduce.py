"""The trace reduction on a hand-built trace whose answers are known.

Device 0 runs, inside a window of [1000, 2000] ns:
  fusion.1 [1000, 1300], a while loop [1400, 1800] holding fusion.2
  [1400, 1500] and collective-permute-done.1 [1500, 1700] (which overlaps
  fusion.3 [1600, 1650]); plus an op outside the window [2100, 2200].
Device 1 runs all-reduce.7 [1000, 1500] alone.
Busy: device 0 300 + 400 = 700 ns, device 1 500 ns -> idle share 0.4.
Exposed collective: device 0 has 1500-1600 and 1650-1700 (150 ns; the
loop that holds the permute does not hide it), device 1 500 ns.
"""

from jax.profiler import ProfileData

from bench import trace_reduce as tr


def _event(meta: int, start: int, dur: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start * 1000} "
            f"duration_ps: {dur * 1000} }}")


def _device(idx: int, ops: list[tuple[str, int, int]]) -> str:
    meta = "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                     f'name: "%{name} = f32[8] op()" }} }}'
                     for i, (name, _, _) in enumerate(ops))
    evs = " ".join(_event(i + 1, s, d) for i, (_, s, d) in enumerate(ops))
    return (f'planes {{ id: {idx + 10} name: "/device:TPU:{idx}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {evs} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 '
            f'{_event(1, 0, 5000)} }} {meta} }}')


def _host(spans: list[tuple[str, int, int]]) -> str:
    meta = "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                     f'name: "{name}" }} }}' for i, (name, _, _) in enumerate(spans))
    evs = " ".join(_event(i + 1, s, d) for i, (_, s, d) in enumerate(spans))
    return (f'planes {{ id: 1 name: "/host:CPU" lines {{ id: 1 name: "python3" '
            f'timestamp_ns: 0 {evs} }} {meta} }}')


def trace() -> tr.Reduced:
    dev0 = [("fusion.1", 1000, 300), ("while.3", 1400, 400),
            ("fusion.2", 1400, 100), ("collective-permute-done.1", 1500, 200),
            ("fusion.3", 1600, 50), ("fusion.4", 2100, 100)]
    dev1 = [("all-reduce.7", 1000, 500)]
    host = [("bench.window", 1000, 1000), ("bench.tick", 1250, 200),
            ("other.span", 1300, 50)]
    text = "\n".join([_host(host), _device(0, dev0), _device(1, dev1)])
    return tr.reduce(ProfileData.from_text_proto(text))


def test_window_and_devices():
    red = trace()
    assert red.window == (1000.0, 2000.0)
    assert [d.index for d in red.devices] == [0, 1]
    assert [s.name for s in red.spans] == ["bench.window", "bench.tick"]


def test_busy_and_idle_share():
    red = trace()
    assert tr.busy_ns(red.devices[0], red.window) == 700.0
    assert tr.busy_ns(red.devices[1], red.window) == 500.0
    assert abs(tr.idle_share(red) - 0.4) < 1e-12


def test_exposed_collective():
    red = trace()
    assert tr.exposed_ns(red.devices[0], red.window) == 150.0
    assert tr.exposed_ns(red.devices[1], red.window) == 500.0


def test_loops_are_not_leaves():
    red = trace()
    leaf = {o.name: o.leaf for o in red.devices[0].ops}
    assert not leaf["while.3"]
    assert leaf["fusion.2"] and leaf["fusion.3"]
    names = [n for n, _ in tr.top_ops(red)]
    assert "while" not in names and names[0] == "all-reduce"


def test_idle_gaps_named_by_innermost_span():
    red = trace()
    gaps = tr.idle_gaps(red)
    # device 0 idles 1300-1400 (inside bench.tick) and 1800-2000
    assert gaps[0][0] == "outside spans" and abs(gaps[0][1] - 2e-7) < 1e-15
    assert gaps[1][0] == "bench.tick" and abs(gaps[1][1] - 1e-7) < 1e-15
