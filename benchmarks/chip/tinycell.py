"""Cells cut to a size a CPU test run holds, for the benchmark's tests:
the same files, with the namespace and the load scaled down."""
from __future__ import annotations

import copy

from cellspec import Cell, find_cell


#: warm-up seconds of a tiny cell's run
TINY_WARMUP_S = 0.5


def tiny(name: str, *, trace: bool = False, trees: int = 2,
         rate: float = 150.0) -> Cell:
    cell = find_cell(name, trace=trace)
    cell.config = copy.deepcopy(cell.config)
    for p in cell.config["namespace"]["parts"]:
        p["trees"] = min(p["trees"], trees)
    cell.traffic = dict(cell.traffic, rate_ops_per_s=rate)
    return cell
