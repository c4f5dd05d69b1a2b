"""Finds everything a cell is made of by the names in ``BENCHMARK.json``.

A cell names its configuration and its traffic; a configuration's file
is the one ``configs`` lists for it, a cell's traffic is
``traffic/<name>.json`` (its rate, the name of its mix, and optionally
``scheduled``: fixed ops on directories of ``traffic: false`` namespace
parts, each ``{"op", "path", "at_s", "phase": "warmup"|"window",
"args"}``, see ``workgen.Scheduled``), the mix it
names is ``mixes/<mix>.json``, and a metric is ``metrics/<name>.py``
(loaded by path, since metric names hold dots). A metric split by the
end-to-end metric it moves (``device.idle_share.steady``) falls back to
the reader of its quantity (``device.idle_share``) when it has none of
its own. Nothing here names a particular cell, mix or metric, so a later
change adds one by adding files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: List[dict]        # the metric entries this cell reports


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, *, trace: bool, root: Path = ROOT,
              here: Path = HERE) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = load_traffic(w["traffic"], here)
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind] if _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, metrics)


def load_traffic(name: str, here: Path = HERE) -> dict:
    """``traffic/<name>.json`` with the rows of the mix it names; its
    other keys (``scheduled`` among them) pass through as they are."""
    traffic = json.loads((here / "traffic" / f"{name}.json").read_text())
    mix = json.loads((here / "mixes" / f"{traffic['mix']}.json").read_text())
    return dict(traffic, mix_name=traffic["mix"], mix=mix["mix"])


def reader_path(metric: str, here: Path = HERE) -> Path:
    """``metrics/<metric>.py``, else the reader of the quantity the name
    splits (the name without its last ``.part``)."""
    name = metric
    while True:
        path = here / "metrics" / f"{name}.py"
        if path.is_file() or "." not in name:
            return path
        name = name.rsplit(".", 1)[0]


def load_reader(metric: str, here: Path = HERE) -> ModuleType:
    """The reader module of ``metric`` (:func:`reader_path`); it defines
    ``read(ctx) -> Optional[float]``."""
    path = reader_path(metric, here)
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(metrics: List[dict], ctx: object,
                 here: Path = HERE) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for every metric whose reader found
    something to read; a reader that returns None leaves its metric out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = load_reader(m["name"], here).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
