"""Cells, configurations, traffic mixes and metrics are found by name: a
cell and a metric added as data only (new files and entries, no code
edit) are found and read."""
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

from cellspec import (find_cell, load_benchmark, load_traffic, read_metrics,
                      reader_path)

HERE = Path(__file__).resolve().parent


def test_every_named_file_exists():
    bench = load_benchmark()
    for c in bench["configs"]:
        assert (HERE.parents[1] / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        for trace in (False, True):
            cell = find_cell(w["name"], trace=trace)
            assert cell.metrics, (w["name"], trace)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert reader_path(m["name"]).is_file()


def test_data_only_cell_and_metric_are_found(tmp_path):
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE / "configs", here / "configs")
    shutil.copytree(HERE / "traffic", here / "traffic")
    shutil.copytree(HERE / "mixes", here / "mixes")
    shutil.copytree(HERE / "metrics", here / "metrics")
    bench = load_benchmark()
    bench["workloads"].append(
        {"name": "spotify-1m.dummy", "config": "spotify-1m",
         "traffic": "dummy-mix", "chips": 1, "why": "lookup test"})
    bench["per_layer"].append(
        {"name": "dummy.ops_dispatched", "unit": "ops", "better": "higher",
         "source": "program_counter", "layer": "Client facade",
         "moves": "p99_ms", "workloads": ["spotify-1m.dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (here / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"mix": "spotify-table1", "rate_ops_per_s": 123.0}))
    (here / "metrics" / "dummy.ops_dispatched.py").write_text(
        "def read(ctx):\n    return ctx.window.dispatched\n")
    cell = find_cell("spotify-1m.dummy", trace=True, root=tmp_path,
                     here=here)
    assert cell.traffic["rate_ops_per_s"] == 123.0
    assert cell.config["name"] == "spotify-1m"
    names = [m["name"] for m in cell.metrics]
    assert "dummy.ops_dispatched" in names
    assert "pkval_roofline" not in names       # listed for another cell
    ctx = SimpleNamespace(window=SimpleNamespace(dispatched=42), trace=None,
                          counters={}, served=[], peak={})
    got = read_metrics([m for m in cell.metrics
                        if m["name"] == "dummy.ops_dispatched"], ctx, here)
    assert got == {"dummy.ops_dispatched": {"value": 42, "unit": "ops"}}


def test_reader_with_nothing_to_read_is_left_out():
    cell = find_cell("spotify-1m.steady", trace=True)
    ctx = SimpleNamespace(trace=None, counters={}, served=[], peak={},
                          window=SimpleNamespace(latencies_s=lambda s: []))
    assert read_metrics(cell.metrics, ctx) == {}


def test_split_metric_falls_back_to_its_quantity_reader(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "device.idle_share.py").write_text("")
    (tmp_path / "metrics" / "device.idle_share.tail.py").write_text("")
    assert reader_path("device.idle_share.steady", tmp_path).name == \
        "device.idle_share.py"
    assert reader_path("device.idle_share.tail", tmp_path).name == \
        "device.idle_share.tail.py"
    assert not reader_path("nothing.here", tmp_path).is_file()


def test_cells_of_one_mix_share_its_rows():
    steady = load_traffic("spotify-table1-steady")
    saturate = load_traffic("spotify-table1-saturate")
    assert steady["mix"] == saturate["mix"]
    assert steady["mix_name"] == saturate["mix_name"] == "spotify-table1"
    assert steady["rate_ops_per_s"] < saturate["rate_ops_per_s"]
