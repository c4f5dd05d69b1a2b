"""The control: the plain reference put in the program's place with one
guarantee broken, run at a cell's own size and load, to show that the
comparison deciding ``correct`` fails it.

    python benchmarks/chip/control.py --workload NAME --seeds S1,S2,... \\
        [--seconds S] [--lag L]

The broken guarantee is serializability of reads: the control answers
every read from a replica of its namespace that lags the acknowledged
writes by ``--lag`` seconds (a read replica, the step a faster read path
would tempt), and every mutation correctly. Its answers and its final
state then go through the same judge as a run of the program. The
benchmark's own runs never run it; it needs no chip (it touches no
device), and prints one JSON line per seed with the compared numbers.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path
from typing import Any, Deque, List, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from cellspec import find_cell  # noqa: E402
from nsplan import NamespacePlan  # noqa: E402
from openloop import drive  # noqa: E402
from refmodel import READ_OPS, RefFS  # noqa: E402
from run_cell import BLOCK_S, WARMUP_S, WINDOW_CAP, WORK_SEED  # noqa: E402
from verdict import RefView, judge  # noqa: E402


class LaggedReplicaService:
    """Serves a batch as the program would, but answers reads from a
    replica that applies each write ``lag`` seconds after it was
    acknowledged."""

    def __init__(self, plan: NamespacePlan, lag: float, clock=time.monotonic):
        self.state = RefFS(plan)
        self.replica = RefFS(plan)
        self.lag = lag
        self.clock = clock
        self.pending: Deque[Tuple[float, Any]] = collections.deque()

    def serve(self, batch: List[Any]) -> List[Tuple[Any, Any]]:
        now = self.clock()
        while self.pending and self.pending[0][0] <= now - self.lag:
            _, w = self.pending.popleft()
            self.replica.apply(w.op, w.path, w.path2, dict(w.args))
        out = []
        for w in batch:
            if w.op in READ_OPS:
                out.append(self.replica.apply(w.op, w.path, w.path2,
                                              dict(w.args)))
            else:
                out.append(self.state.apply(w.op, w.path, w.path2,
                                            dict(w.args)))
                self.pending.append((now, w))
        return out


def run_control(config: dict, traffic: dict, seed: int, seconds: float,
                lag: float, warmup_seed: str = "warmup",
                warmup_s: float = WARMUP_S) -> dict:
    """One run of the control in the program's place; the judge's
    numbers and how much it compared."""
    from workgen import make_generator
    plan = NamespacePlan(config["namespace"]["parts"])
    gen = make_generator(config, traffic, plan)
    svc = LaggedReplicaService(plan, lag)
    rate, cap = float(traffic["rate_ops_per_s"]), WINDOW_CAP
    windows: list = []
    ops: list = []

    def serve(lo: int, hi: int) -> list:
        batch = ops[lo:hi]
        answers = svc.serve(batch)
        windows.append(list(zip(batch, answers)))
        return answers

    warm_due, ops = gen.schedule(warmup_seed, rate, warmup_s,
                                 phase="warmup")
    drive(warm_due, serve, warmup_s, cap=cap)
    due, ops = gen.schedule(str(seed), rate, seconds,
                            work_seed=WORK_SEED, block_s=BLOCK_S,
                            phase="window")
    win = drive(due, serve, seconds, cap=cap)
    v = judge(plan, windows, RefView(svc.state), seed,
              scheduled=[s.path for s in gen.scheduled])
    out = {k: val for k, (val, _) in v.numbers().items()}
    out.update(seed=seed, dispatched=win.dispatched, calls=len(win.calls),
               compared=v.compared_ops, uncompared=v.uncompared_reads,
               examples=v.examples[:2])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--lag", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    cell = find_cell(args.workload, trace=False)
    for s in args.seeds.split(","):
        res = run_control(cell.config, cell.traffic, int(s), args.seconds,
                          args.lag)
        res["workload"] = args.workload
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
