"""Finds a configuration's knee: the highest offered rate at which the
backlog stops growing over the window.

    python benchmarks/chip/sweep.py --workload NAME --rates R1,R2,... \\
        [--seconds S]

One process loads the cell's configuration and warms up as a run does,
then offers each rate in turn (ascending, on the same cluster) for
``--seconds`` and prints one JSON line per rate: ops due, dispatched and
served, the served rate, the backlog at the end and at half time, and
p50/p99 latency. Rates later in the sweep meet warmer hint caches than a
fresh run does, so the knee it finds is, if anything, low. The cell's
rates are written into its traffic file by hand from this output.
"""
import argparse
import bisect
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from cellspec import find_cell  # noqa: E402
from nsplan import NamespacePlan  # noqa: E402
from openloop import drive, percentile  # noqa: E402
from refmodel import ANSWER_ERRORS, normalize  # noqa: E402
from run_cell import CONCURRENT, WARMUP_S, WINDOW_CAP  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}",
          flush=True)
    if dev.platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 1
    from repro.core import DFSClient
    from repro.kernels import mode
    from chipload import build_cluster
    from warmup import warm_kernels
    from workgen import make_generator
    mode.use_compile_cache()
    cell = find_cell(args.workload, trace=False)
    cap = WINDOW_CAP
    plan = NamespacePlan(cell.config["namespace"]["parts"])
    gen = make_generator(cell.config, cell.traffic, plan)
    store, cluster = build_cluster(cell.config, plan)
    client = DFSClient(cluster)
    ops: list = []

    def serve(lo, hi):
        batch = ops[lo:hi]
        st = client.run_trace(batch, planned=True, concurrent=CONCURRENT,
                              adaptive=False, window=len(batch))
        return [normalize(w.op, o.ok, o.error,
                          o.result.value if o.ok else None)
                for w, o in zip(batch, st.outcomes)]

    rates = [float(r) for r in args.rates.split(",")]
    due, ops = gen.schedule("warmup", rates[0], WARMUP_S)
    drive(due, serve, WARMUP_S, cap=cap)
    warm_kernels(store, cluster, client, cap, print)
    gc.collect()
    gc.freeze()
    for k, rate in enumerate(rates):
        due, ops = gen.schedule(f"sweep-{k}", rate, args.seconds)
        half = []

        def at_half(call, due=due, half=half):
            if not half and call.end >= args.seconds / 2:
                half.append(bisect.bisect_right(due, call.end) - call.hi)
        t0 = time.perf_counter()
        w = drive(due, serve, args.seconds, cap=cap, on_call=at_half)
        served = [a[0] is None or a[0] in ANSWER_ERRORS for a in w.answers]
        lat = w.latencies_s(served)
        print(json.dumps({
            "workload": args.workload, "rate": rate, "due": len(due),
            "dispatched": w.dispatched, "served": sum(served),
            "served_per_s": sum(served) / w.seconds,
            "backlog_half": half[0] if half else None,
            "backlog_end": w.backlog, "calls": len(w.calls),
            "mean_call_ops": w.dispatched / max(1, len(w.calls)),
            "p50_ms": 1000 * percentile(lat, 50) if lat else None,
            "p99_ms": 1000 * percentile(lat, 99) if lat else None,
            "client_cache": len(client.hint_cache.export_entries()),
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
