"""Runs one benchmark cell once, on the chip it is started on.

    python benchmarks/chip/run_cell.py --workload NAME --seed N \\
        --seconds S --trace 0|1

Set-up (timed from process start): find the cell's files by name, load
the configuration's namespace into a 4-namenode columnar cluster, warm
up at the cell's rate on a fixed seed stream, compile every kernel shape
bucket a window can use, draw the window's schedule from ``--seed``.
The traffic file's scheduled ops (``workgen.Scheduled``) go into the
warm-up and the window at their offsets; each is logged with its
due-to-return time, and readers find them in ``ctx.scheduled``, and
the stats of every subtree op the window ran in ``ctx.subtree``.
Then the open-loop window drives ``DFSClient.run_trace(planned=True,
concurrent=False, adaptive=False)`` for ``--seconds``. After it, the
plain reference judges every answer and the store (``verdict.py``).

The last stdout line is the result as JSON; the numbers compared, each
with its limit, are the last stderr lines and the result's last key.
With ``--trace 1`` the window (and the reference check after it) runs
under the profiler and the per-layer metrics are reported instead of
the end-to-end ones. Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 1.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Iterator  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from cellspec import Cell, find_cell, read_metrics  # noqa: E402
from devtrace import (  # noqa: E402
    load_events, maybe_annotate, profile_options, reduce)
from nsplan import NamespacePlan  # noqa: E402
from openloop import drive  # noqa: E402
from refmodel import ANSWER_ERRORS, normalize  # noqa: E402
from workcount import peaks  # noqa: E402

#: the warm-up's seed stream: the same for every run, so set-up is the
#: same work whatever ``--seed`` is
WARMUP_SEED = "warmup"
#: seconds of warm-up traffic at the cell's rate, before the kernel buckets
WARMUP_S = 10.0
#: most ops handed to one call: ``chip_smoke.py``'s window, at least
#: ``PHASH_MIN_BATCH`` (512), so every kernel gate can open
WINDOW_CAP = 1024
#: the window's ops and gaps are one tape drawn from this seed; ``--seed``
#: deals the tape's blocks of ``BLOCK_S`` seconds in its own order, so
#: every seed offers the same work
WORK_SEED = "20161606"
BLOCK_S = 2.0
#: the sequential planned path: ``concurrent=True`` races in the
#: program's ``expand_wave`` (PERF.md, Open questions)
CONCURRENT = False


def log(line: str) -> None:
    print(line, flush=True)


class CompileCounter:
    """Counts jit traces (new shapes) and backend compiles (cache misses)
    through ``jax.monitoring``."""

    def __init__(self):
        self.traced = 0
        self.compiled = 0

    def __call__(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traced += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def kernel_probes() -> dict:
    """The program's per-family launch/demotion counters (as read by
    chip_smoke.py); a family the program no longer has is left out."""
    from repro.core import batch_planner, columnar, namenode
    found = {"phash_chain": getattr(batch_planner, "_phash_chain_probe", None),
             "phash": getattr(namenode, "_phash_probe", None),
             "hintchain": getattr(columnar, "_hintchain_probe", None),
             "pkval": getattr(columnar, "_pkval_probe", None),
             "treeagg": getattr(columnar, "_treeagg_probe", None)}
    return {k: v for k, v in found.items() if v is not None}


@contextlib.contextmanager
def observe_planner(sink: list) -> Iterator[None]:
    """Collect the ``PlanReport`` of every planned pipeline run (the
    pipeline ``run_trace`` builds is not returned to the caller)."""
    from repro.core import batch_planner
    cls = batch_planner.PlannedRequestPipeline
    run = cls.run

    def observed(self, wops):
        try:
            return run(self, wops)
        finally:
            if self.plan_report is not None:
                sink.append(self.plan_report)
    cls.run = observed
    try:
        yield
    finally:
        cls.run = run


#: the program's subtree operations (``SubtreeOps`` methods) observed
SUBTREE_OPS = ("delete_subtree", "chmod_subtree", "chown_subtree")


@contextlib.contextmanager
def observe_subtree(sink: list) -> Iterator[None]:
    """After every subtree op a namenode runs, collect its op, path, host
    seconds and ``SubtreeOps.last_stats`` (waves, rows scanned, peak
    frontier, phase-3 chunks), which the next subtree op resets."""
    from repro.core.subtree import SubtreeOps
    real = {name: getattr(SubtreeOps, name) for name in SUBTREE_OPS}

    def observed(name, fn):
        def op(self, path, *args, **kw):
            t = time.perf_counter()
            try:
                return fn(self, path, *args, **kw)
            finally:
                stats = {k: v for k, v in self.last_stats.items()
                         if k != "chunk_costs"}
                sink.append(dict(stats, op=name, path=path,
                                 seconds=time.perf_counter() - t))
        return op
    for name, fn in real.items():
        setattr(SubtreeOps, name, observed(name, fn))
    try:
        yield
    finally:
        for name, fn in real.items():
            setattr(SubtreeOps, name, fn)


def counters(cluster, store, probes: dict, reports: list) -> dict:
    out = {
        "round_trips": sum(nn.agg_cost.round_trips
                           for nn in cluster.namenodes),
        "nn_pkval_probes": sum(nn.pkval_probes for nn in cluster.namenodes),
        "nn_treeagg_demotions": sum(nn.treeagg_demotions
                                    for nn in cluster.namenodes),
        "lock_waits": store.locks.wait_count,
        "lock_acquires": store.locks.acquire_count,
        "planned_ops": sum(r.ops for r in reports),
        "pinned_ops": sum(r.pinned_ops for r in reports),
        "planner_pkval_probes": sum(r.pkval_probes for r in reports),
    }
    for fam, p in probes.items():
        out[f"{fam}.launches"] = p.launches
        out[f"{fam}.demotions"] = p.demotions
        out[f"{fam}.h2d_bytes"] = p.h2d_bytes
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        peak: dict, warmup_s: float = WARMUP_S) -> dict:
    """Set up, run the window, judge it; returns the result line."""
    import jax
    from repro.core import DFSClient
    from chipload import build_cluster
    from verdict import StoreView, judge
    from warmup import warm_kernels
    from workgen import make_generator

    devs = jax.devices()
    dev = devs[0]
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    rate, cap = float(cell.traffic["rate_ops_per_s"]), WINDOW_CAP

    plan = NamespacePlan(cell.config["namespace"]["parts"])
    gen = make_generator(cell.config, cell.traffic, plan)
    store, cluster = build_cluster(cell.config, plan)
    client = DFSClient(cluster)
    log(f"set-up: loaded {store.table('inode').n_rows} inodes at "
        f"{time.perf_counter() - T_PROCESS:.3f} s")
    reports: list = []
    probes = kernel_probes()
    c_start = counters(cluster, store, probes, reports)
    ops: list = []
    windows: list = []          # [(wop, answer)] per call, for the verdict
    subtree: list = []          # every subtree op's stats (observe_subtree)

    def serve(lo: int, hi: int) -> list:
        batch = ops[lo:hi]
        with maybe_annotate("run_trace", trace):
            st = client.run_trace(batch, planned=True,
                                  concurrent=CONCURRENT, adaptive=False,
                                  window=len(batch))
        with maybe_annotate("dispatch", trace):
            answers = [normalize(w.op, o.ok, o.error,
                                 o.result.value if o.ok else None)
                       for w, o in zip(batch, st.outcomes)]
            windows.append(list(zip(batch, answers)))
        return answers

    def wait(s: float) -> None:
        with maybe_annotate("wait_arrivals", trace):
            time.sleep(s)

    with observe_planner(reports), observe_subtree(subtree):
        warm_due, ops = gen.schedule(WARMUP_SEED, rate, warmup_s,
                                     phase="warmup")
        warm = drive(warm_due, serve, warmup_s, cap=cap, sleep=wait)
        log(f"set-up: warm-up served {warm.dispatched} ops in "
            f"{len(warm.calls)} calls, backlog {warm.backlog}, at "
            f"{time.perf_counter() - T_PROCESS:.3f} s")
        warm_kernels(store, cluster, client, cap, log)
        log(f"set-up: kernel buckets warmed at "
            f"{time.perf_counter() - T_PROCESS:.3f} s")
        due, ops = gen.schedule(str(seed), rate, seconds,
                                work_seed=WORK_SEED, block_s=BLOCK_S,
                                phase="window")
        scheduled = [(k, s.op, s.path) for k, s in gen.placed]
        n_warm_windows = len(windows)
        c0 = counters(cluster, store, probes, reports)
        n_subtree0 = len(subtree)
        traced0, compiled0 = compiles.traced, compiles.compiled
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if trace else ""
        # settle the heap: a full collection of the millions of objects
        # set-up made would otherwise land in the window at random; the
        # loaded namespace lives as long as the process, so it is frozen
        # out of later collections
        gc.collect()
        gc.freeze()
        index = store.table("inode").hindex
        log(f"window opens: inode index {index.cap} slots, client hint "
            f"cache {len(client.hint_cache.export_entries())} entries")
        # name any program compiled inside the window (there should be none)
        jax.config.update("jax_log_compiles", True)
        setup_s = time.perf_counter() - T_PROCESS
        log(f"set-up: {setup_s:.3f} s; window: {len(ops)} ops due over "
            f"{seconds} s at {rate} ops/s")
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options())
        try:
            with maybe_annotate("window", trace):
                win = drive(due, serve, seconds, cap=cap, sleep=wait)
            jax.config.update("jax_log_compiles", False)
            c1 = counters(cluster, store, probes, reports)
            win_subtree = subtree[n_subtree0:]
            traced = compiles.traced - traced0
            compiled = compiles.compiled - compiled0
            mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
            with maybe_annotate("reference_check", trace):
                t_ref = time.perf_counter()
                verdict = judge(plan, windows, StoreView(store), seed,
                                scheduled=[s.path for s in gen.scheduled])
                ref_s = time.perf_counter() - t_ref
        finally:
            if trace:
                jax.profiler.stop_trace()
    reduced = None
    if trace:
        reduced = reduce(load_events(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None:
            raise RuntimeError("the trace holds no window span")

    timed = windows[n_warm_windows:]
    answers = [a for w in timed for _, a in w]
    served = [a[0] is None or a[0] in ANSWER_ERRORS for a in answers]
    delta = {k: c1[k] - c0[k] for k in c1}
    demotions = sum(c1[k] - c_start[k] for k in c1
                    if k.endswith("demotions"))
    log(f"window: {win.dispatched} ops dispatched in {len(win.calls)} "
        f"calls over {win.seconds:.6f} s; {sum(served)} served")
    log(f"backlog at the end: {win.backlog} ops due and not dispatched")
    log(f"window closes: inode index {store.table('inode').hindex.cap} "
        f"slots, client hint cache "
        f"{len(client.hint_cache.export_entries())} entries")
    log(f"compiles in window: traced={traced} compiled={compiled}")
    log(f"device memory peak: {mem} bytes")
    log(f"counters over the window: {json.dumps(delta, sort_keys=True)}")
    log(f"reference check: {verdict.compared_ops} answers compared, "
        f"{verdict.uncompared_reads} reads not compared, "
        f"{verdict.unserved} ops not served, {ref_s:.3f} s")
    for ex in verdict.examples:
        log(f"mismatch: {ex}")
    for k, op, path in scheduled:
        call = next((c for c in win.calls if c.lo <= k < c.hi), None)
        if call is None:
            log(f"scheduled {op} {path}: due {due[k]:.6f} s, not dispatched")
            continue
        ran = [s for s in win_subtree if s["op"] == op and s["path"] == path]
        stats = "".join(f"; {key} {val}" for key, val in ran[-1].items()
                        if key not in ("op", "path")) if ran else ""
        log(f"scheduled {op} {path}: due {due[k]:.6f} s, returned after "
            f"{call.end - due[k]:.6f} s, {answers[k][0] or 'ok'}{stats}")

    ctx = SimpleNamespace(window=win, served=served, setup_s=setup_s,
                          counters=delta, trace=reduced, peak=peak,
                          scheduled=scheduled, subtree=win_subtree)
    numbers = verdict.numbers()
    numbers["kernel_demotions"] = (demotions, 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": all(v <= lim for v, lim in numbers.values()),
              "attempted": win.dispatched,
              "failed": len(served) - sum(served),
              "metrics": read_metrics(cell.metrics, ctx), "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print(f"run_cell: no TPU found (JAX backend is {dev.platform!r}); "
              f"the benchmark runs only on the chip", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.kernels import mode
    except ImportError as e:
        print(f"run_cell: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 1
    cell = find_cell(args.workload, trace=bool(args.trace))
    if len(devs) < cell.chips:
        print(f"run_cell: {cell.name} needs {cell.chips} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    peak = peaks(dev.device_kind)
    log(f"compile cache: {mode.use_compile_cache()}")
    result = run(cell, args.seed, args.seconds, bool(args.trace), peak)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
