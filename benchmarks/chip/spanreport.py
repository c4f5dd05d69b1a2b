"""Runs one cell traced, as ``run_cell.py --trace 1`` does, keeping the
program's own spans in the trace, and prints what they show.

    python benchmarks/chip/spanreport.py --workload NAME --seed N \\
        --seconds S [--excerpt PATH [--excerpt-at S] [--excerpt-ms MS]]

It is ``run_cell.py`` with a few of its names swapped
(:func:`spans_kept`): the trace is loaded and reduced by ``spantrace``,
the context the metric readers get is kept, and each
``DFSClient.run_trace`` call records, per op, how long the op's answer
waited between the return of the batch that served it
(``OpOutcome.done_s``) and the return of the call. After
``run_cell.py``'s own lines it prints
``idle attributed to program spans: X of Y s`` (the device idle time
inside ``run_trace`` that program spans cover, of all of it), and its
last stdout line is JSON: the readings of :func:`readings` and the nested
breakdown. A program without the spans or counters leaves the readings
that need them null. ``--excerpt`` writes the events of ``--excerpt-ms``
of the window, from ``--excerpt-at`` seconds into it, as a test excerpt.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Iterator, List, Optional

import run_cell
import spantrace
from openloop import percentile

sys.path.insert(0, str(run_cell.ROOT / "src"))


@contextlib.contextmanager
def spans_kept() -> Iterator[SimpleNamespace]:
    """Swap ``run_cell``'s trace loader and reducer and metric reading,
    and ``DFSClient.run_trace``, for versions that also keep what
    :func:`readings` needs; yields where they keep it (``events``,
    ``ctx``, ``calls``: per call, each op's held seconds or None)."""
    from repro.core import DFSClient
    seen = SimpleNamespace(events=None, ctx=None, calls=[])
    saved = {k: getattr(run_cell, k) for k in
             ("load_events", "reduce", "read_metrics")}
    run_trace = DFSClient.run_trace

    def load_events(log_dir):
        seen.events = spantrace.load_events(log_dir)
        return seen.events

    def read_metrics(metrics, ctx, *a, **kw):
        seen.ctx = ctx
        return saved["read_metrics"](metrics, ctx, *a, **kw)

    def timed_run_trace(self, wops, **kw):
        st = run_trace(self, wops, **kw)
        ret = time.perf_counter()
        seen.calls.append([None if getattr(o, "done_s", None) is None
                           else ret - o.done_s for o in st.outcomes])
        return st

    run_cell.load_events, run_cell.reduce = load_events, spantrace.reduce
    run_cell.read_metrics = read_metrics
    DFSClient.run_trace = timed_run_trace
    try:
        yield seen
    finally:
        for k, v in saved.items():
            setattr(run_cell, k, v)
        DFSClient.run_trace = run_trace


def held_s(seen: SimpleNamespace) -> List[float]:
    """Held seconds of every op served in the window: the window's calls
    are the last ones made (the reference check calls nothing)."""
    calls = seen.calls[len(seen.calls) - len(seen.ctx.window.calls):]
    per_op = [h for c in calls for h in c]
    return [h for h, ok in zip(per_op, seen.ctx.served)
            if ok and h is not None]


def _layer_sum(table: dict, prefix: str) -> Optional[float]:
    vals = [v for k, v in table.items() if k.startswith(prefix)]
    return sum(vals) if vals else None


def readings(ctx: SimpleNamespace, held: List[float]) -> dict:
    """The per-layer readings of the program's spans and counters over
    the window; None where there is nothing to read."""
    t = ctx.trace or {}
    c = ctx.counters
    served = sum(ctx.served)
    planned = c.get("planned_ops", 0)
    planner = _layer_sum(t.get("span_self_s", {}), "planner.")
    namenode = _layer_sum(t.get("span_self_s", {}), "namenode.")
    h2d = [v for k, v in c.items() if k.endswith(".h2d_bytes")]
    k_span = _layer_sum(t.get("span_s", {}), "kernel.")
    k_idle = _layer_sum(t.get("span_device_idle_s", {}), "kernel.")
    return {
        "planner.host_us_per_op": (1e6 * planner / planned
                                   if planner is not None and planned
                                   else None),
        "namenode.host_us_per_op": (1e6 * namenode / served
                                    if namenode is not None and served
                                    else None),
        "kernels.h2d_kib_per_op": (sum(h2d) / served / 1024
                                   if h2d and served else None),
        "kernels.launch_idle_share": (100.0 * k_idle / k_span
                                      if k_span else None),
        "client.held_ms_p50": (1000.0 * percentile(held, 50)
                               if held else None),
    }


def excerpt(events: List[dict], at_s: float, ms: float) -> List[dict]:
    """The events overlapping ``ms`` of the window from ``at_s`` seconds
    into it, with a ``window`` span of just that slice; times counted
    from the slice's start."""
    w0 = min(e["start_ns"] for e in events if e["name"] == "window"
             and not e["plane"].startswith("/device:"))
    a = w0 + int(at_s * 1e9)
    b = a + int(ms * 1e6)
    out = [{"plane": "/host:CPU", "line": "python3", "name": "window",
            "start_ns": 0, "dur_ns": b - a, "thread": 0}]
    for e in events:
        if e["name"] != "window" and e["start_ns"] < b \
                and e["start_ns"] + e["dur_ns"] > a:
            out.append(dict(e, start_ns=e["start_ns"] - a))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", default="")
    ap.add_argument("--excerpt-at", type=float, default=20.0)
    ap.add_argument("--excerpt-ms", type=float, default=450.0)
    args = ap.parse_args(argv)
    with spans_kept() as seen:
        rc = run_cell.main(["--workload", args.workload, "--seed",
                            str(args.seed), "--seconds", str(args.seconds),
                            "--trace", "1"])
    if rc != 0 or seen.ctx is None:
        return rc or 1
    t = seen.ctx.trace
    in_run = t["span_device_idle_s"].get("run_trace", 0.0)
    run_cell.log(f"idle attributed to program spans: "
                 f"{t['idle_program_s']:.6f} of {in_run:.6f} s")
    report = {
        "readings": readings(seen.ctx, held_s(seen)),
        "ops_served_per_s": sum(seen.ctx.served) / seen.ctx.window.seconds,
        "idle_program_s": t["idle_program_s"],
        "idle_run_trace_s": in_run,
        "idle_gaps": t["idle_gaps"],
        "span_self_s": t["span_self_s"],
        "span_s": t["span_s"],
        "span_device_idle_s": t["span_device_idle_s"],
        "counters": seen.ctx.counters,
    }
    if args.excerpt:
        path = Path(args.excerpt)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({
            "note": f"excerpt of a TPU trace of {args.workload} "
                    f"(spanreport.py): {args.excerpt_ms} ms of the window "
                    f"from {args.excerpt_at} s into it, with the harness's "
                    f"and the program's spans",
            "events": excerpt(seen.events, args.excerpt_at,
                              args.excerpt_ms)}, separators=(",", ":")))
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
