"""Cells cut to a size a CPU test run holds, for the benchmark's tests:
the same files, with the namespace and the load scaled down."""
from __future__ import annotations

import copy

from cellspec import Cell, find_cell, load_benchmark
from run_cell import WARMUP_S


#: warm-up seconds of a tiny cell's run
TINY_WARMUP_S = 0.5
#: window seconds of a tiny cell's run
TINY_WINDOW_S = 1.5
#: most files in a directory of a ``traffic: false`` part
TINY_FILES_PER_DIR = 2000


def tiny(name: str, *, trace: bool = False, **cut) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, cut by :func:`shrink`."""
    return shrink(find_cell(name, trace=trace), **cut)


def shrink(cell: Cell, *, trees: int = 2, rate: float = 150.0,
           window_s: float = TINY_WINDOW_S) -> Cell:
    """``cell`` with at most ``trees`` trees in each namespace part, at
    most ``TINY_FILES_PER_DIR`` files per directory of a ``traffic:
    false`` part, traffic at ``rate``, and each scheduled op at the same
    share of a ``TINY_WARMUP_S`` warm-up or a ``window_s`` window as of
    the full run's."""
    cell.config = copy.deepcopy(cell.config)
    for p in cell.config["namespace"]["parts"]:
        p["trees"] = min(p["trees"], trees)
        if not p.get("traffic", False):
            p["files_per_dir"] = min(p["files_per_dir"], TINY_FILES_PER_DIR)
    share = {"warmup": TINY_WARMUP_S / WARMUP_S,
             "window": window_s / float(load_benchmark()["run_seconds"])}
    scheduled = [dict(s, at_s=s["at_s"] * share.get(s["phase"], 1.0))
                 for s in cell.traffic.get("scheduled", ())]
    cell.traffic = dict(cell.traffic, rate_ops_per_s=rate)
    if scheduled:
        cell.traffic["scheduled"] = scheduled
    return cell
