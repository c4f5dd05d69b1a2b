"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig8]
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import paper_benches as pb            # noqa: E402
from benchmarks.trace_replay import bench_trace_replay  # noqa: E402
from repro.kernels.mode import use_compile_cache      # noqa: E402

BENCHES = [
    ("table1", pb.bench_table1_workload_mix),
    ("fig2a", pb.bench_fig2a_opcosts),
    ("fig6", pb.bench_fig6_raw_throughput),
    ("fig7", pb.bench_fig7_subtree),
    ("table2", pb.bench_table2_capacity),
    ("fig8", pb.bench_fig8_industrial),
    ("fig9", pb.bench_fig9_latency),
    ("fig10", pb.bench_fig10_p99),
    ("fig11", pb.bench_fig11_failover),
    ("fig12_13", pb.bench_fig12_13_ablations),
    ("table3", pb.bench_table3_costmodel),
    ("trace_replay", bench_trace_replay),
    ("ckpt", pb.bench_ckpt_metadata),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    use_compile_cache()

    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
        except Exception as e:  # pragma: no cover
            print(f"{name}.ERROR,0,{type(e).__name__}: {e}")
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.2f},\"{derived}\"")
        print(f"{name}.elapsed,{(time.time() - t0) * 1e6:.0f},"
              f"\"{time.time() - t0:.1f}s wall\"")


if __name__ == "__main__":
    main()
