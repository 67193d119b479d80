"""The harness finds every cell's files by the names in ``BENCHMARK.json``,
so a new configuration, traffic mix or per-layer metric is a new file and a
new entry, never an edit of a file that is there."""

import json
import shutil

import pytest

from bench import harness


def spec():
    return json.loads((harness.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = harness.load_cell(cell)
    assert c.config and c.traffic and c.end_to_end and c.per_layer
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    harness.load_driver(c)
    for m in c.per_layer:
        assert (harness.BENCH / "layer_metrics" / f"{m['name']}.py").exists()


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A copy of the benchmark gains a cell, a traffic mix and a per-layer
    metric by adding files and entries alone."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench")
    s = spec()
    s["workloads"].append({"name": "stencil-8192-1x1", "config": "stencil2d-f32",
                           "traffic": "stencil-8192-1x1", "chips": 1,
                           "why": "a new size"})
    for m in s["end_to_end"]:
        if m["name"] == "stencil_gcells_per_s":
            m["workloads"].append("stencil-8192-1x1")
    s["per_layer"].append({"name": "stencil.new_share", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "stencil step",
                           "moves": "stencil_gcells_per_s",
                           "workloads": ["stencil-8192-1x1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    traffic = json.loads((harness.BENCH / "traffic" / "stencil-4096-2x2.json")
                         .read_text())
    traffic.update(grid=[1, 1], domain=[8192, 8192])
    (root / "bench" / "traffic" / "stencil-8192-1x1.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "layer_metrics" / "stencil.new_share.py").write_text(
        "def read(ctx):\n    return None\n")
    c = harness.load_cell("stencil-8192-1x1", root)
    assert c.traffic["domain"] == [8192, 8192]
    assert [m["name"] for m in c.per_layer] == ["stencil.new_share"]
    assert [m["name"] for m in c.end_to_end] == ["stencil_gcells_per_s", "setup_s"]
    # the new entry changes no line of a cell that was there
    assert "stencil.new_share" not in [m["name"] for m in
                                       harness.load_cell("stencil-4096-2x2", root).per_layer]


def test_a_per_layer_metric_lists_its_cells(tmp_path):
    """A per-layer entry without ``workloads`` is refused, so that no new
    entry attaches itself to the cells that were there."""
    s = spec()
    s["per_layer"].append({"name": "x", "unit": "%", "better": "higher",
                           "source": "device_trace", "layer": "device",
                           "moves": "stencil_gcells_per_s"})
    with pytest.raises(harness.BenchError, match="list no workloads"):
        harness.load_cell("stencil-4096-2x2", spec=s)


def test_no_tpu_means_no_result(capsys):
    """Off the chip the command exits non-zero and prints no result line."""
    assert harness.main(["--workload", "stencil-4096-2x2", "--seed", "1",
                         "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.accelerator(1)
