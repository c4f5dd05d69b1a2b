"""Spotify-trace replay driver — the paper's Fig 7 throughput-scaling
methodology (§7.2).

Replays a fixed Spotify-style trace (§7.2 op mix: ~67% getBlockLocations,
~12% listStatus, ...) through the batched multi-namenode request pipeline at
several namenode counts and writes a Fig 7-style throughput-vs-namenodes
JSON. Two layers are exercised:

  * the **DES** (`BatchedHopsFSSim`): cluster-scale throughput/latency with
    per-op DB round-trip profiles measured from the functional store;
  * the **functional pipeline**, driven through the typed `DFSClient`
    facade (`DFSClient.run_trace` -> `RequestPipeline`): real transactions
    on the real store, proving the batched executor's round-trip savings
    and that batched == sequential final state — on the Spotify mix AND
    on the write-heavy block-layer mix (`WRITE_HEAVY_MIX`), where the
    lease-ordered grouped block-write path carries the batched share
    (`batched_write_fraction`). Four execution modes per mix: sequential,
    reactive, planned (closed-loop: response-piggybacked client hint
    cache + adaptive windows) and planned+concurrent (per-window worker
    fleet with lease-ordered dealing), with client hint-cache hit-rate
    telemetry.

A ``failover`` section (§7.6, Fig 11) kills one of four namenodes
mid-replay on the DES with fine-grained timeline bins and reports the
throughput dip depth, time/ops to recovery and the number of zero-
throughput bins (paper: none — clients fail over transparently).

  PYTHONPATH=src python -m benchmarks.trace_replay [--quick] \
      [--out BENCH_throughput.json] [--namenodes 1,4,16] [--batch-size 16]

Output schema is documented in docs/BENCHMARKS.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import (DFSClient, MetadataStore, NamenodeCluster,
                        format_fs, materialize_namespace, namespace_snapshot)
from repro.core.cluster_sim import BatchedHopsFSSim, profile_ops
from repro.core.workload import (NamespaceSpec, SPOTIFY_TRACE_MIX,
                                 SyntheticNamespace, TraceReplay,
                                 WRITE_HEAVY_MIX, make_spotify_trace)
from repro.kernels.mode import use_compile_cache

Row = Tuple[str, float, str]

DEFAULT_OUT = Path(__file__).resolve().parents[1] / "BENCH_throughput.json"


def replay_des(trace, profiles, *, n_namenodes: int, n_ndb: int = 8,
               batch_size: int = 16, clients_per_nn: int = 200,
               horizon: float = 0.3, seed: int = 1,
               planned: bool = False) -> Dict:
    """Replay the trace at one namenode count on the batched-pipeline DES
    (``planned=True`` mirrors the client-side batch planner: partition-
    aligned, type-pure batch pulls instead of FIFO slices)."""
    sim = BatchedHopsFSSim(n_namenodes=n_namenodes, n_ndb=n_ndb,
                           profiles=profiles, batch_size=batch_size,
                           seed=seed, planned=planned)
    sim.start_clients(clients_per_nn * n_namenodes, TraceReplay(trace))
    res = sim.run(horizon)
    return {
        "namenodes": n_namenodes,
        "clients": clients_per_nn * n_namenodes,
        "planned": planned,
        "throughput_ops_s": round(res.throughput, 1),
        "latency_avg_ms": round(res.latency_avg() * 1e3, 3),
        "latency_p99_ms": round(res.latency_pct(99) * 1e3, 3),
        "completed_ops": res.completed,
        "batches_executed": sim.batches_executed,
        "batched_ops": sim.batched_ops,
        "per_nn_ops": list(sim.nn_ops_completed),
    }


def functional_batching_report(trace, *, n_namenodes: int = 4,
                               batch_size: int = 16,
                               n_dirs: int = 20) -> Dict:
    """Run the *functional* pipeline four ways on identical stores —
    sequential (batch=1), reactive (FIFO batches, opportunistic grouping),
    planned (closed-loop client-side batch planner: partition-aligned,
    type-sorted batches with grouped reads AND writes, response-warmed
    client hint cache, adaptive windows) and planned+concurrent (one
    worker per namenode within each window barrier) — and report measured
    round-trip savings, batched fractions, local round-trip share,
    client hint-cache hit rates, and final-state equivalence. Ties the
    DES's collapse model to real transactions; driven through the typed
    `DFSClient` facade."""
    from repro.core import PlannedRequestPipeline

    def build():
        store = MetadataStore(n_datanodes=4)
        format_fs(store)
        cluster = NamenodeCluster(store, n_namenodes)
        ns = SyntheticNamespace(NamespaceSpec(), n_dirs=n_dirs,
                                files_per_dir=4)
        materialize_namespace(cluster.namenodes[0], ns)
        return store, cluster

    store_seq, cluster = build()
    seq = DFSClient(cluster).run_trace(trace, batch_size=1)
    store_rea, cluster = build()
    rea = DFSClient(cluster).run_trace(trace, batch_size=batch_size)
    # start the adaptive window small relative to the trace so the closed
    # loop actually cycles (plan -> execute -> absorb -> replan) several
    # times; the controller grows it from there
    window0 = batch_size * 8
    store_pln, cluster = build()
    planned_pipe = PlannedRequestPipeline(cluster, batch_size=batch_size,
                                          window=window0)
    pln = planned_pipe.run(trace)
    plan = planned_pipe.plan_report
    store_cc, cluster = build()
    cc_pipe = PlannedRequestPipeline(cluster, batch_size=batch_size,
                                     concurrent=True, window=window0)
    cc = cc_pipe.run(trace)
    cc_plan = cc_pipe.plan_report
    # multi-NN dispatch differs between runs, so physical ids and per-NN
    # mtime clocks differ; compare the logical namespace instead (the
    # strict single-NN full-state equality lives in the test suite)
    snap_seq = namespace_snapshot(store_seq)
    state_equal = (snap_seq == namespace_snapshot(store_rea)
                   == namespace_snapshot(store_pln)
                   == namespace_snapshot(store_cc))
    rt_seq = seq.total_cost.round_trips
    rt_rea = rea.total_cost.round_trips
    rt_pln = pln.total_cost.round_trips
    rt_cc = cc.total_cost.round_trips

    def pct(saved, base):
        return round(100 * (1 - saved / base), 2) if base else 0.0

    def hint_telemetry(rep, cache):
        return {
            "client_hits": rep.client_hits if rep else 0,
            "fallback_hits": rep.client_fallback_hits if rep else 0,
            "misses": rep.client_misses if rep else 0,
            "hit_rate": round(rep.hint_hit_rate, 3) if rep else 0.0,
            "stale_overwrites": cache.stale_overwrites,
            "invalidations": cache.invalidations,
            "entries": cache.entries,
        }

    return {
        "batch_size": batch_size,
        "ops": len(seq.outcomes),
        "ok": pln.ok,
        "failed": pln.failed,
        "sequential_round_trips": rt_seq,
        "batched_round_trips": rt_rea,       # back-compat: reactive mode
        "reactive_round_trips": rt_rea,
        "planned_round_trips": rt_pln,
        "round_trip_savings_pct": pct(rt_rea, rt_seq),
        "planned_savings_pct": pct(rt_pln, rt_seq),
        "planned_vs_reactive_savings_pct": pct(rt_pln, rt_rea),
        "batched_fraction": round(rea.batched_fraction, 3),
        "planned_batched_fraction": round(pln.batched_fraction, 3),
        "batched_read_fraction": round(pln.batched_read_fraction, 3),
        "batched_write_fraction": round(pln.batched_write_fraction, 3),
        "local_rt_fraction": {
            "sequential": round(seq.local_rt_fraction, 3),
            "reactive": round(rea.local_rt_fraction, 3),
            "planned": round(pln.local_rt_fraction, 3),
            "planned_concurrent": round(cc.local_rt_fraction, 3),
        },
        "planner": {
            "planned_ops": plan.planned_ops if plan else 0,
            "pinned_ops": plan.pinned_ops if plan else 0,
            "lease_ordered_ops": plan.lease_ordered_ops if plan else 0,
            "windows": plan.windows if plan else 0,
            "window_sizes": list(plan.window_sizes) if plan else [],
            "kernel_launches": plan.kernel_launches if plan else 0,
            "predicted_local_rt_share":
                round(plan.predicted_local_share, 3) if plan else 0.0,
        },
        # closed-loop client hint-cache telemetry (deterministic planned
        # run): hits on the response-warmed client cache vs fallback hits
        # on the merged namenode caches vs misses
        "hint_cache": hint_telemetry(plan, planned_pipe.client_cache),
        # the concurrent planned mode: per-window worker fleet, lifted
        # mutation pinning (lease-ordered dealing), same final namespace
        "planned_concurrent": {
            "ok": cc.ok,
            "failed": cc.failed,
            "round_trips": rt_cc,
            "vs_reactive_savings_pct": pct(rt_cc, rt_rea),
            "batched_fraction": round(cc.batched_fraction, 3),
            "batched_read_fraction": round(cc.batched_read_fraction, 3),
            "batched_write_fraction": round(cc.batched_write_fraction, 3),
            "lease_ordered_ops":
                cc_plan.lease_ordered_ops if cc_plan else 0,
            "pinned_ops": cc_plan.pinned_ops if cc_plan else 0,
            "hint_cache": hint_telemetry(cc_plan, cc_pipe.client_cache),
        },
        "state_matches_sequential": state_equal,
    }


def failover_report(trace, profiles, *, n_namenodes: int = 4,
                    batch_size: int = 16, horizon: float = 0.3,
                    kill_frac: float = 0.4, restart_frac: float = 0.7,
                    timeline_bin: float = 0.02, seed: int = 1) -> Dict:
    """Kill one of ``n_namenodes`` mid-replay on the batched DES, restart
    it later, and measure the throughput dip and recovery (§7.6: HopsFS
    keeps serving through a namenode failure — surviving namenodes drain
    the shared queue and clients requeue in-flight batches, so the dip is
    a brief capacity loss, never HDFS-style downtime)."""
    sim = BatchedHopsFSSim(n_namenodes=n_namenodes, n_ndb=8,
                           profiles=profiles, batch_size=batch_size,
                           seed=seed, timeline_bin=timeline_bin)
    sim.start_clients(200 * n_namenodes, TraceReplay(trace))
    kill_at = round(kill_frac * horizon, 4)
    restart_at = round(restart_frac * horizon, 4)
    victim = 0
    sim.schedule_kill(kill_at, victim)
    sim.schedule_restart(restart_at, victim)
    res = sim.run(horizon)
    counts = dict(res.timeline)
    n_bins = int(horizon / timeline_bin)
    series = [counts.get(b * timeline_bin, 0) for b in range(n_bins)]
    kill_bin = int(kill_at / timeline_bin)
    pre = series[1:kill_bin]             # drop the cold-start bin
    steady = sum(pre) / len(pre) if pre else 0.0
    post = series[kill_bin:]
    dip = min(post) if post else 0
    # recovery = first post-kill bin back at >=90% of steady throughput
    recovery_bin = next(
        (kill_bin + i for i, c in enumerate(post) if c >= 0.9 * steady),
        None)
    recovered = recovery_bin is not None
    recovery_s = (round((recovery_bin - kill_bin + 1) * timeline_bin, 4)
                  if recovered else None)
    ops_to_recovery = (sum(series[kill_bin:recovery_bin + 1])
                       if recovered else sum(post))
    return {
        "n_namenodes": n_namenodes,
        "killed_namenode": victim,
        "kill_at_s": kill_at,
        "restart_at_s": restart_at,
        "horizon_s": horizon,
        "timeline_bin_s": timeline_bin,
        "steady_ops_per_bin": round(steady, 1),
        "dip_ops_per_bin": dip,
        "dip_depth_pct": (round(100 * (1 - dip / steady), 1)
                          if steady else 0.0),
        "recovered": recovered,
        "recovery_s": recovery_s,
        "ops_to_recovery": ops_to_recovery,
        "zero_bins_after_kill": sum(1 for c in post if c == 0),
        "requeued_ops": sim.failed_ops,
        "completed_ops": res.completed,
        "fault_events": [[round(t, 4), action, nn]
                         for t, action, nn in sim.fault_events],
    }


def elasticity_report(trace, profiles, *, batch_size: int = 16,
                      horizon: float = 0.3, timeline_bin: float = 0.02,
                      scale_out_frac: float = 0.3,
                      scale_in_frac: float = 0.7,
                      phase_ops: int = 600, seed: int = 1) -> Dict:
    """The elastic-pool benchmark, two layers like everything else here.

    **DES**: replay on the batched planned pipeline starting at 2
    namenodes, scale out to 4 mid-run and back in to 2 later, with
    fine-grained timeline bins — throughput must RISE through scale-out
    with no zero-throughput bins (joiners pull from the shared queue
    immediately) and return to the 2-NN steady state after scale-in.

    **Functional**: three phases of ONE continuous Spotify stream on the
    real store. Phase A (2 NNs, fixed) measures the steady-state client
    hint hit rate; phase B runs with an ``ElasticNamenodePool`` attached,
    which scales 2→4 under queue pressure (joiners pre-warmed from the
    client cache); idle ticks then scale back 4→2, warm-migrating the
    victims' caches; phase C measures the post-migration hit rate — the
    warm-migration claim is that it stays within a few percent of phase
    A's. The full three-phase namespace must equal a fixed-size
    sequential replay of the same trace (scale events move WORK, never
    metadata)."""
    from repro.core import PlannedRequestPipeline, RequestPipeline
    from repro.core.pool import ElasticNamenodePool
    from repro.core.hint_cache import InodeHintCache
    from repro.core.workload import make_phased_trace

    # -- DES: throughput through scale-out 2->4 and scale-in 4->2 ------
    base_nns, peak_nns = 2, 4
    sim = BatchedHopsFSSim(n_namenodes=base_nns, n_ndb=8,
                           profiles=profiles, batch_size=batch_size,
                           seed=seed, planned=True,
                           timeline_bin=timeline_bin)
    # client population sized for the PEAK fleet, so the base fleet is
    # genuinely oversubscribed and scale-out has headroom to absorb
    sim.start_clients(200 * peak_nns, TraceReplay(trace))
    out_at = round(scale_out_frac * horizon, 4)
    in_at = round(scale_in_frac * horizon, 4)
    sim.schedule_scale_out(out_at, peak_nns - base_nns)
    sim.schedule_scale_in(in_at, peak_nns - base_nns)
    res = sim.run(horizon)
    counts = dict(res.timeline)
    n_bins = int(horizon / timeline_bin)
    series = [counts.get(b * timeline_bin, 0) for b in range(n_bins)]
    out_bin = int(out_at / timeline_bin)
    in_bin = int(in_at / timeline_bin)
    pre = series[1:out_bin]               # drop the cold-start bin
    steady = sum(pre) / len(pre) if pre else 0.0
    # settled scaled-phase throughput: skip the ramp bin after scale-out
    scaled_bins = series[out_bin + 1:in_bin]
    scaled = (sum(scaled_bins) / len(scaled_bins) if scaled_bins else 0.0)
    post = series[in_bin:]
    # recovery after scale-in = first bin back DOWN to within 25% of the
    # 2-NN steady state (the fleet sheds capacity, so "recovered" means
    # settled, not restored)
    rec_bin = next((in_bin + i for i, c in enumerate(post)
                    if c <= 1.25 * steady), None)
    recovered = rec_bin is not None

    # -- functional: warm migration on the real store ------------------
    def build():
        store = MetadataStore(n_datanodes=4)
        format_fs(store)
        cluster = NamenodeCluster(store, base_nns)
        ns = SyntheticNamespace(NamespaceSpec(), n_dirs=20,
                                files_per_dir=4)
        materialize_namespace(cluster.namenodes[0], ns)
        return store, cluster, ns

    store, cluster, ns = build()
    full, bounds = make_phased_trace(ns, [phase_ops] * 3, seed=5)
    a, b, c = (full[:bounds[0]], full[bounds[0]:bounds[1]],
               full[bounds[1]:])
    cache = InodeHintCache()
    window = batch_size * 8

    def run_phase(wops, pool=None):
        pipe = PlannedRequestPipeline(cluster, batch_size=batch_size,
                                      window=window, client_cache=cache,
                                      adaptive=False, pool=pool)
        stats = pipe.run(wops)
        return stats, pipe.plan_report

    stats_a, rep_a = run_phase(a)
    pool = ElasticNamenodePool(cluster, min_namenodes=base_nns,
                               max_namenodes=peak_nns, high_load=60,
                               low_load=20, hysteresis=2, cooldown=2)
    pool.register_client_cache(cache)
    stats_b, rep_b = run_phase(b, pool=pool)
    # drain: idle control rounds scale the fleet back in, warm-migrating
    # each victim's hint cache to the survivors
    for _ in range(32):
        if len(cluster.alive_namenodes()) <= base_nns:
            break
        pool.tick(queue_depth=0)
    stats_c, rep_c = run_phase(c)
    ok = stats_a.ok + stats_b.ok + stats_c.ok
    failed = stats_a.failed + stats_b.failed + stats_c.failed

    # fixed-size sequential oracle over the SAME full trace
    store_seq, cluster_seq, _ = build()
    RequestPipeline(cluster_seq, batch_size=1).run(full)
    state_equal = (namespace_snapshot(store)
                   == namespace_snapshot(store_seq))

    before = rep_a.hint_hit_rate
    after = rep_c.hint_hit_rate
    return {
        "n_namenodes_base": base_nns,
        "n_namenodes_peak": peak_nns,
        "scale_out_at_s": out_at,
        "scale_in_at_s": in_at,
        "horizon_s": horizon,
        "timeline_bin_s": timeline_bin,
        "steady_ops_per_bin": round(steady, 1),
        "scaled_ops_per_bin": round(scaled, 1),
        "scale_out_gain_pct": (round(100 * (scaled / steady - 1), 1)
                               if steady else 0.0),
        "zero_bins_during_scale_out": sum(
            1 for v in series[out_bin:in_bin] if v == 0),
        "scale_in_recovered": recovered,
        "scale_in_recovery_s": (round((rec_bin - in_bin + 1)
                                      * timeline_bin, 4)
                                if recovered else None),
        "completed_ops": res.completed,
        "scale_events": [[round(t, 4), action, nn]
                         for t, action, nn in sim.fault_events],
        # functional warm-migration phases
        "phase_ops": phase_ops,
        "ok": ok,
        "failed": failed,
        "hint_hit_rate_before": round(before, 3),
        "hint_hit_rate_after": round(after, 3),
        "hint_hit_rate_drop_pct": (round(100 * (1 - after / before), 1)
                                   if before else 0.0),
        "hint_routed_batches": (rep_b.hint_routed_batches
                                + rep_c.hint_routed_batches),
        "migrated_hint_entries": pool.migrated_entries,
        "pool_scale_outs": pool.scale_outs,
        "pool_scale_ins": pool.scale_ins,
        "pool_events": [[e.t, e.action, e.nn_id, e.migrated_entries]
                        for e in pool.events],
        "state_matches_sequential": state_equal,
    }


def overload_report(*, batch_size: int = 16, n_ops: int = 600,
                    n_tenants: int = 6, n_namenodes: int = 3,
                    deadline_budget: int = 8, deadline_per_op: float = 0.05,
                    delay_ticks: int = 6, seed: int = 9) -> Dict:
    """Gray-failure overload bench (docs/ROBUSTNESS.md): one namenode
    turns SLOW (alive, heartbeating, every batch exchange with it ages
    the shared logical clock — the chaos ``DELAY`` kind) while a Zipf
    s≈1.1 multi-tenant trace with per-op deadlines replays through the
    planned pipeline. Two runs on identical stores:

      * **unprotected** — the plain planned pipeline. The planner keeps
        dealing to the slow namenode, the clock races ahead of the
        deadline horizon, and ops complete LATE (past their deadline —
        work nobody is waiting for).
      * **protected** — admission controller + breaker board. The slow
        namenode sheds already-expired work (``DeadlineExpired``), the
        shed batches trip its circuit breaker, the planner reroutes
        around it, and the clock stops racing. Nothing completes past
        its deadline (admission is checked AFTER the exchange's clock
        advance, so the guarantee is exact, not statistical).

    Goodput is ``ok AND completed_at <= deadline`` on the election
    clock. The protected run must beat the unprotected run on goodput
    and on worst per-tenant p99, with zero late completions. A recovery
    pass (breaker healed, deadlines inert) then re-drives shed ops and
    the final namespace must equal the fault-free sequential oracle —
    shedding loses timeliness, never metadata."""
    from repro.core import (AdmissionController, BreakerBoard, ChaosPlan,
                            DELAY, Fault, FaultInjector, FaultSite,
                            PlannedRequestPipeline, RequestPipeline,
                            stamp_deadlines)
    from repro.core.chaos import RETRYABLE_ERRORS
    from repro.core.workload import make_zipf_tenant_trace

    def build():
        store = MetadataStore(n_datanodes=4)
        format_fs(store)
        cluster = NamenodeCluster(store, n_namenodes)
        ns = SyntheticNamespace(NamespaceSpec(), n_dirs=20,
                                files_per_dir=4)
        materialize_namespace(cluster.namenodes[0], ns)
        return store, cluster, ns

    def fresh_trace(ns, now):
        trace = make_zipf_tenant_trace(ns, n_ops, n_tenants=n_tenants,
                                       seed=seed)
        return stamp_deadlines(trace, now=now, budget=deadline_budget,
                               per_op=deadline_per_op)

    def injector(cluster):
        # one gray-slow namenode: every batch exchange with NN 1 ages the
        # shared clock by ``delay_ticks`` while the slowdown is active
        plan = ChaosPlan(faults=[Fault(FaultSite.BATCH_EXCHANGE, at=4,
                                       victim=1, kind=DELAY,
                                       heal_after=10_000,
                                       delay_ticks=delay_ticks)])
        return FaultInjector(plan, cluster)

    def measure(trace, outcomes, now0):
        ok = late = good = 0
        per_tenant: Dict[str, List[int]] = {}
        shed: Dict[str, int] = {}
        for wop, oc in zip(trace, outcomes):
            if oc.ok:
                ok += 1
                done = oc.result.completed_at
                if wop.deadline is not None and done is not None \
                        and done > wop.deadline:
                    late += 1
                else:
                    good += 1
                per_tenant.setdefault(wop.tenant, []).append(
                    (done if done is not None else now0) - now0)
            else:
                shed[oc.error] = shed.get(oc.error, 0) + 1

        def p99(xs):
            return sorted(xs)[min(len(xs) - 1, int(0.99 * len(xs)))]

        p99s = {t: p99(xs) for t, xs in sorted(per_tenant.items())}
        return {
            "ok": ok,
            "goodput_ops": good,
            "goodput_frac": round(good / len(trace), 3),
            "late_completions": late,
            "failed_by_error": dict(sorted(shed.items())),
            "per_tenant_p99_ticks": p99s,
            "worst_tenant_p99_ticks": max(p99s.values()) if p99s else 0,
            "clock_advance_ticks": None,   # filled by caller
        }

    window = batch_size * 4

    # -- unprotected: naive planned pipeline under the gray failure -----
    store_u, cluster_u, ns_u = build()
    now0_u = cluster_u.election.now
    trace_u = fresh_trace(ns_u, now0_u)
    inj_u = injector(cluster_u)
    inj_u.install()
    try:
        pipe_u = PlannedRequestPipeline(cluster_u, batch_size=batch_size,
                                        window=window, adaptive=False)
        stats_u = pipe_u.run(trace_u)
    finally:
        inj_u.uninstall()
    unprotected = measure(trace_u, stats_u.outcomes, now0_u)
    unprotected["clock_advance_ticks"] = cluster_u.election.now - now0_u

    # -- protected: admission + breakers on an identical cluster --------
    store_p, cluster_p, ns_p = build()
    now0_p = cluster_p.election.now
    trace_p = fresh_trace(ns_p, now0_p)
    admission = AdmissionController(cluster_p.election,
                                    queue_capacity=max(n_ops, 1))
    admission.install(cluster_p)
    board = BreakerBoard(cluster_p.election, failure_threshold=1,
                         reset_after=64)
    inj_p = injector(cluster_p)
    inj_p.install()
    try:
        pipe_p = PlannedRequestPipeline(cluster_p, batch_size=batch_size,
                                        window=window, adaptive=False,
                                        admission=admission,
                                        breakers=board)
        stats_p = pipe_p.run(trace_p)
    finally:
        inj_p.uninstall()
    protected = measure(trace_p, stats_p.outcomes, now0_p)
    protected["clock_advance_ticks"] = cluster_p.election.now - now0_p

    # -- recovery: slow NN healed, deadlines inert — shed ops re-driven;
    # shedding must cost timeliness only, never metadata
    admission.uninstall()
    outcomes = list(stats_p.outcomes)
    todo = [i for i, oc in enumerate(outcomes)
            if not oc.ok and oc.error in RETRYABLE_ERRORS]
    if todo:
        rstats = RequestPipeline(cluster_p, batch_size=1).run(
            [trace_p[i] for i in todo])
        for i, oc in zip(todo, rstats.outcomes):
            outcomes[i] = oc
    cluster_p.recover_leases()
    cluster_p.scrub_leases()

    # fault-free sequential oracle over the same logical trace
    store_o, cluster_o, ns_o = build()
    trace_o = fresh_trace(ns_o, cluster_o.election.now)
    RequestPipeline(cluster_o, batch_size=1).run(trace_o)
    state_equal = (namespace_snapshot(store_p)
                   == namespace_snapshot(store_o))

    rep_p = pipe_p.plan_report
    return {
        "n_namenodes": n_namenodes,
        "slow_namenode": 1,
        "delay_ticks_per_exchange": delay_ticks,
        "n_ops": n_ops,
        "n_tenants": n_tenants,
        "zipf_s": 1.1,
        "batch_size": batch_size,
        "deadline_budget_ticks": deadline_budget,
        "deadline_per_op_ticks": deadline_per_op,
        "unprotected": unprotected,
        "protected": protected,
        "goodput_gain_pct": (
            round(100 * (protected["goodput_ops"]
                         / max(1, unprotected["goodput_ops"]) - 1), 1)),
        "planner_deadline_shed": rep_p.deadline_shed,
        "planner_breaker_rerouted": rep_p.breaker_rerouted,
        "breaker_trips": board.trips,
        "breaker_open_at_end": sorted(board.open_ids()),
        "admission": admission.report(),
        "recovery_redriven_ops": len(todo),
        "state_matches_sequential": state_equal,
    }


def columnar_report(*, batch_size: int = 16, n_ops: int = 600,
                    n_namenodes: int = 4, n_dirs: int = 20) -> Dict:
    """Differential columnar-engine bench (docs/ARCHITECTURE.md, columnar
    section): replay the Spotify mix AND the write-heavy block mix
    through the planned pipeline twice on identical setups — once on the
    dict-backed ``MetadataStore`` oracle, once on the struct-of-arrays
    ``ColumnarMetadataStore`` — then assert the two final states are
    byte-identical (``dump_state`` equality, the oracle lock) and report
    the fused-kernel economics: ONE hintchain launch resolves a whole
    planner window's hint chains and ONE pkval launch validates its
    client-resolved PKs, so launches must be orders of magnitude rarer
    than ops."""
    from repro.core import PlannedRequestPipeline
    from repro.core.columnar import ColumnarMetadataStore

    def build(store_cls):
        store = store_cls(n_datanodes=4)
        format_fs(store)
        cluster = NamenodeCluster(store, n_namenodes)
        ns = SyntheticNamespace(NamespaceSpec(), n_dirs=n_dirs,
                                files_per_dir=4)
        materialize_namespace(cluster.namenodes[0], ns)
        return store, cluster

    window = batch_size * 8
    modes: Dict[str, Dict] = {}
    agg = {"hintchain_launches": 0, "pkval_launches": 0, "pkval_probes": 0,
           "pkval_demotions": 0, "treeagg_launches": 0,
           "treeagg_demotions": 0}
    total_ops = 0
    wall_dict = wall_col = 0.0
    state_all = True
    for mode, mix_kw in (("spotify", {}),
                         ("write_heavy", {"mix": WRITE_HEAVY_MIX})):
        ns = SyntheticNamespace(NamespaceSpec(), n_dirs=n_dirs,
                                files_per_dir=4)
        trace = make_spotify_trace(ns, n_ops, seed=5, **mix_kw)
        runs: Dict[str, Dict] = {}
        for backend, cls in (("dict", MetadataStore),
                             ("columnar", ColumnarMetadataStore)):
            store, cluster = build(cls)
            pipe = PlannedRequestPipeline(cluster, batch_size=batch_size,
                                          window=window)
            t0 = time.time()
            stats = pipe.run(list(trace))
            wall = time.time() - t0
            rep = pipe.plan_report
            runs[backend] = {
                "store": store,
                "wall": wall,
                "windows": rep.windows,
                "ok": stats.ok,
                "failed": stats.failed,
                "hintchain_launches": rep.hintchain_launches,
                "pkval_launches": rep.pkval_launches
                + sum(nn.pkval_launches for nn in cluster.namenodes),
                "pkval_probes": rep.pkval_probes
                + sum(nn.pkval_probes for nn in cluster.namenodes),
                "pkval_demotions": rep.pkval_demotions
                + sum(nn.pkval_demotions for nn in cluster.namenodes),
                "treeagg_launches": sum(nn.treeagg_launches
                                        for nn in cluster.namenodes),
                "treeagg_demotions": sum(nn.treeagg_demotions
                                         for nn in cluster.namenodes),
            }
        d, c = runs["dict"], runs["columnar"]
        # the oracle lock: bit-identical rows, PKs and costs aside from
        # nothing — the columnar engine is a LAYOUT, not a behaviour
        state_equal = (d["store"].dump_state() == c["store"].dump_state())
        state_all = state_all and state_equal
        windows = max(1, c["windows"])
        modes[mode] = {
            "ops": len(trace),
            "ok": c["ok"],
            "failed": c["failed"],
            "windows": c["windows"],
            "hintchain_launches": c["hintchain_launches"],
            "pkval_launches": c["pkval_launches"],
            "pkval_probes": c["pkval_probes"],
            "pkval_demotions": c["pkval_demotions"],
            "treeagg_launches": c["treeagg_launches"],
            "treeagg_demotions": c["treeagg_demotions"],
            "window_ms_dict": round(1e3 * d["wall"]
                                    / max(1, d["windows"]), 2),
            "window_ms_columnar": round(1e3 * c["wall"] / windows, 2),
            "state_matches_oracle": state_equal,
        }
        for k in agg:
            agg[k] += modes[mode][k]
        total_ops += len(trace)
        wall_dict += d["wall"]
        wall_col += c["wall"]
    fused = agg["hintchain_launches"] + agg["pkval_launches"]
    return {
        "batch_size": batch_size,
        "window": window,
        "n_namenodes": n_namenodes,
        "ops": total_ops,
        "modes": modes,
        "hintchain_launches": agg["hintchain_launches"],
        "pkval_launches": agg["pkval_launches"],
        "pkval_probes": agg["pkval_probes"],
        "pkval_demotions": agg["pkval_demotions"],
        "treeagg_launches": agg["treeagg_launches"],
        "treeagg_demotions": agg["treeagg_demotions"],
        "fused_launches": fused,
        "launches_per_op": round(fused / max(1, total_ops), 4),
        "wall_s_dict": round(wall_dict, 2),
        "wall_s_columnar": round(wall_col, 2),
        "state_matches_oracle": state_all,
    }


def big_dir_report(*, n_children: int = 100_000, n_ops: int = 400,
                   batch_size: int = 1000, seed: int = 23) -> Dict:
    """Million-entry-directory bench (paper §6: subtree ops as "many small
    parallel transactions" that do NOT stall the cluster).

    One flat directory of ``n_children`` files is deleted through the
    incremental subtree protocol while a BIG_DIR_MIX side trace keeps
    running — the delete's pace hook replays one adjacent op between
    every chunk commit, so the reported paced p50/p99 are latencies
    *measured while the subtree op holds its lock*, compared against the
    identical mix with no subtree op running.  Run on both backends: the
    dict oracle and the columnar store (whose du aggregation + phase-2
    wave advisory launch the fused treeagg kernel), with ``dump_state``
    byte-equality across backends AND incremental-vs-legacy as the locks.
    """
    from repro.core import materialize_big_dir
    from repro.core.columnar import ColumnarMetadataStore
    from repro.core.ops_registry import WorkloadOp
    from repro.core.workload import BIG_DIR_MIX, make_big_dir_namespace

    def build(store_cls, n_kids):
        store = store_cls(n_datanodes=4)
        format_fs(store)
        cluster = NamenodeCluster(store, 1)
        nn = cluster.namenodes[0]
        ns, big, _ = make_big_dir_namespace(n_kids)
        materialize_namespace(nn, ns)
        materialize_big_dir(nn, big, n_kids)
        nn.subtree.batch_size = batch_size
        return store, nn, ns, big

    def pct(lat, q):
        if not lat:
            return 0.0
        s = sorted(lat)
        return round(s[min(len(s) - 1, int(q * len(s)))] * 1e3, 3)

    def run_op(nn, wop, lat):
        t0 = time.perf_counter()
        try:
            nn.invoke(wop)
            ok = True
        except Exception:
            ok = False
        lat.append(time.perf_counter() - t0)
        return ok

    runs: Dict[str, Dict] = {}
    for backend, cls in (("dict", MetadataStore),
                         ("columnar", ColumnarMetadataStore)):
        store, nn, ns, big = build(cls, n_children)
        # identical traces on both backends: same ns plan, same seeds
        base_trace = make_spotify_trace(ns, n_ops, seed=seed,
                                        mix=BIG_DIR_MIX)
        paced_trace = make_spotify_trace(ns, n_ops, seed=seed + 1,
                                         mix=BIG_DIR_MIX)
        base_lat: List[float] = []
        for wop in base_trace:
            run_op(nn, wop, base_lat)
        paced_lat: List[float] = []
        it = iter(paced_trace)
        paces = [0]
        busy = [False]       # re-entrancy guard: a paced op must never
                             # drive the pace hook again

        def pace():
            if busy[0]:
                return
            wop = next(it, None)
            if wop is None:
                return
            busy[0] = True
            try:
                paces[0] += 1
                run_op(nn, wop, paced_lat)
            finally:
                busy[0] = False

        nn.subtree.pace = pace
        t0 = time.time()
        res = nn.invoke(WorkloadOp("delete_subtree", big, on_dir=True))
        wall = time.time() - t0
        nn.subtree.pace = None
        for wop in it:       # drain: both backends run the full trace
            run_op(nn, wop, paced_lat)
        runs[backend] = {
            "store": store,
            "wall": wall,
            "deleted": res.value["deleted"],
            "stats": dict(nn.subtree.last_stats),
            "paces": paces[0],
            "base_p50": pct(base_lat, 0.50), "base_p99": pct(base_lat, 0.99),
            "paced_p50": pct(paced_lat, 0.50),
            "paced_p99": pct(paced_lat, 0.99),
            "treeagg_launches": nn.treeagg_launches,
            "treeagg_demotions": nn.treeagg_demotions,
        }
    d, c = runs["dict"], runs["columnar"]
    state_equal = d["store"].dump_state() == c["store"].dump_state()

    # incremental vs legacy differential: same (smaller) build + trace on
    # two dict stores, only the phase-2/3 machinery differs
    n_small = max(1000, n_children // 10)
    dumps = []
    for incremental in (True, False):
        store, nn, ns, big = build(MetadataStore, n_small)
        nn.subtree.incremental = incremental
        for wop in make_spotify_trace(ns, min(n_ops, 100), seed=seed + 2,
                                      mix=BIG_DIR_MIX):
            try:
                nn.invoke(wop)
            except Exception:
                pass
        nn.invoke(WorkloadOp("delete_subtree", big, on_dir=True))
        dumps.append(store.dump_state())
    inc_equal = dumps[0] == dumps[1]

    st = c["stats"]
    return {
        "n_children": n_children,
        "total_inodes": n_children + 1,
        "batch_size": batch_size,
        "deleted": c["deleted"],
        "chunks": st["chunks"],
        "waves": st["waves"],
        "peak_frontier": st["peak_frontier"],
        "subtree_wall_s_dict": round(d["wall"], 2),
        "subtree_wall_s_columnar": round(c["wall"], 2),
        "adjacent_ops": n_ops,
        "pace_invocations": c["paces"],
        "baseline_p50_ms": c["base_p50"],
        "baseline_p99_ms": c["base_p99"],
        "paced_p50_ms": c["paced_p50"],
        "paced_p99_ms": c["paced_p99"],
        "p99_ratio": round(c["paced_p99"] / max(c["base_p99"], 1e-9), 2),
        "treeagg_launches": c["treeagg_launches"],
        "treeagg_demotions": c["treeagg_demotions"],
        "state_matches_oracle": state_equal,
        "incremental_matches_legacy": inc_equal,
    }


def run_replay(*, quick: bool = False, namenode_counts=(1, 4, 16),
               batch_size: int = 16, trace_ops: int = 5000,
               seed: int = 11) -> Dict:
    horizon = 0.15 if quick else 0.3
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=60)
    trace = make_spotify_trace(ns, trace_ops if not quick else 2000,
                               seed=seed)
    profiles = profile_ops()
    points = []
    for n in namenode_counts:
        pt = replay_des(trace, profiles, n_namenodes=n,
                        batch_size=batch_size, horizon=horizon)
        planned_pt = replay_des(trace, profiles, n_namenodes=n,
                                batch_size=batch_size, horizon=horizon,
                                planned=True)
        pt["planned_throughput_ops_s"] = planned_pt["throughput_ops_s"]
        pt["planned_batched_ops"] = planned_pt["batched_ops"]
        points.append(pt)
    # speedup vs the smallest namenode count actually measured (only
    # "vs 1 NN" when the sweep includes 1, e.g. the default 1,4,16)
    base_pt = min(points, key=lambda p: p["namenodes"])
    base = base_pt["throughput_ops_s"] or 1.0
    for pt in points:
        pt["speedup_vs_min_nn"] = round(pt["throughput_ops_s"] / base, 2)
        pt["baseline_namenodes"] = base_pt["namenodes"]
    func = functional_batching_report(
        make_spotify_trace(SyntheticNamespace(NamespaceSpec(), n_dirs=20,
                                              files_per_dir=4),
                           300 if quick else 600, seed=5),
        batch_size=batch_size)
    # the lease-ordered grouped block-write path under an ingest-shaped
    # mix: create/add_block/complete/append dominate, so
    # batched_write_fraction is the headline here
    func_w = functional_batching_report(
        make_spotify_trace(SyntheticNamespace(NamespaceSpec(), n_dirs=20,
                                              files_per_dir=4),
                           300 if quick else 600, seed=5,
                           mix=WRITE_HEAVY_MIX),
        batch_size=batch_size)
    failover = failover_report(trace, profiles, batch_size=batch_size,
                               horizon=horizon)
    elasticity = elasticity_report(trace, profiles, batch_size=batch_size,
                                   horizon=horizon,
                                   phase_ops=300 if quick else 600)
    overload = overload_report(batch_size=batch_size,
                               n_ops=300 if quick else 600)
    columnar = columnar_report(batch_size=batch_size,
                               n_ops=300 if quick else 600)
    big_dir = big_dir_report(n_children=4000 if quick else 100_000,
                             n_ops=150 if quick else 400)
    return {
        "benchmark": "trace_replay_throughput",
        "paper_figure": "Fig 7 (throughput vs number of namenodes)",
        "trace": {
            "mix": [{"op": op, "weight_pct": w, "dir_fraction": d}
                    for op, w, d in SPOTIFY_TRACE_MIX],
            "n_ops": len(trace),
            "seed": seed,
        },
        "write_heavy_mix": [{"op": op, "weight_pct": w, "dir_fraction": d}
                            for op, w, d in WRITE_HEAVY_MIX],
        "params": {
            "batch_size": batch_size,
            "n_ndb": 8,
            "horizon_s": horizon,
            "quick": quick,
        },
        "scaling": points,
        "functional_batching": func,
        "functional_batching_write_heavy": func_w,
        "failover": failover,
        "elasticity": elasticity,
        "overload": overload,
        "columnar": columnar,
        "big_dir": big_dir,
    }


def bench_trace_replay(quick: bool = False) -> List[Row]:
    """Row-formatted entry point for benchmarks/run.py."""
    report = run_replay(quick=quick,
                        namenode_counts=(1, 4) if quick else (1, 4, 16))
    rows: List[Row] = []
    for pt in report["scaling"]:
        rows.append((f"trace_replay.hops_{pt['namenodes']}nn", 0.0,
                     f"{pt['throughput_ops_s']:,.0f} ops/s "
                     f"({pt['speedup_vs_min_nn']}x vs "
                     f"{pt['baseline_namenodes']} NN)"))
    f = report["functional_batching"]
    rows.append(("trace_replay.functional_savings", 0.0,
                 f"{f['round_trip_savings_pct']}% fewer DB round trips "
                 f"at batch={f['batch_size']} "
                 f"(state match: {f['state_matches_sequential']})"))
    bd = report["big_dir"]
    rows.append(("trace_replay.big_dir", 0.0,
                 f"paced delete of {bd['total_inodes']:,} inodes: "
                 f"adjacent p99 x{bd['p99_ratio']}, "
                 f"{bd['treeagg_launches']} treeagg launches "
                 f"(oracle match: {bd['state_matches_oracle']})"))
    rows.append(("trace_replay.planner_savings", 0.0,
                 f"planned {f['planned_vs_reactive_savings_pct']}% fewer "
                 f"RTs vs reactive; batched "
                 f"{f['planned_batched_fraction']} "
                 f"(writes {f['batched_write_fraction']}), local RT "
                 f"{f['local_rt_fraction']['planned']}"))
    w = report["functional_batching_write_heavy"]
    rows.append(("trace_replay.write_heavy_block_path", 0.0,
                 f"write-heavy: batched writes "
                 f"{w['batched_write_fraction']}, planned "
                 f"{w['planned_vs_reactive_savings_pct']}% fewer RTs vs "
                 f"reactive (state match: "
                 f"{w['state_matches_sequential']})"))
    wc = w["planned_concurrent"]
    rows.append(("trace_replay.planned_concurrent", 0.0,
                 f"concurrent planned: batched writes "
                 f"{wc['batched_write_fraction']}, "
                 f"{wc['vs_reactive_savings_pct']}% fewer RTs vs reactive, "
                 f"hint hit rate {wc['hint_cache']['hit_rate']}"))
    fo = report["failover"]
    rows.append(("trace_replay.failover", 0.0,
                 f"kill 1/{fo['n_namenodes']} NN mid-replay: dip "
                 f"{fo['dip_depth_pct']}%, recovery {fo['recovery_s']} s "
                 f"({fo['ops_to_recovery']} ops), "
                 f"{fo['zero_bins_after_kill']} zero bins (paper: none)"))
    ov = report["overload"]
    rows.append(("trace_replay.overload", 0.0,
                 f"gray-slow NN: goodput "
                 f"{ov['unprotected']['goodput_frac']} -> "
                 f"{ov['protected']['goodput_frac']} protected, late "
                 f"{ov['unprotected']['late_completions']} -> "
                 f"{ov['protected']['late_completions']}, "
                 f"{ov['breaker_trips']} breaker trips (state match: "
                 f"{ov['state_matches_sequential']})"))
    co = report["columnar"]
    rows.append(("trace_replay.columnar", 0.0,
                 f"columnar engine: {co['fused_launches']} fused launches "
                 f"for {co['ops']} ops ({co['launches_per_op']}/op), "
                 f"{co['pkval_probes']} PKs validated, state match: "
                 f"{co['state_matches_oracle']}"))
    el = report["elasticity"]
    rows.append(("trace_replay.elasticity", 0.0,
                 f"scale-out {el['n_namenodes_base']}->"
                 f"{el['n_namenodes_peak']} NN: +{el['scale_out_gain_pct']}%"
                 f" throughput, {el['zero_bins_during_scale_out']} zero "
                 f"bins; hint hit rate {el['hint_hit_rate_before']} -> "
                 f"{el['hint_hit_rate_after']} after warm migration "
                 f"(state match: {el['state_matches_sequential']})"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT)
    ap.add_argument("--namenodes", default="1,4,16",
                    help="comma-separated namenode counts")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--only", choices=("big_dir",),
                    help="run a single report section (CI uses this to "
                         "regenerate one section without touching the "
                         "committed artifact)")
    args = ap.parse_args()
    use_compile_cache()

    if args.only == "big_dir":
        t0 = time.time()
        bd = big_dir_report(n_children=4000 if args.quick else 100_000,
                            n_ops=150 if args.quick else 400)
        bd["wall_s"] = round(time.time() - t0, 1)
        args.out.write_text(json.dumps({"big_dir": bd}, indent=2) + "\n")
        _print_big_dir(bd)
        print(f"wrote {args.out}")
        return

    counts = tuple(int(x) for x in args.namenodes.split(","))
    t0 = time.time()
    report = run_replay(quick=args.quick, namenode_counts=counts,
                        batch_size=args.batch_size)
    report["wall_s"] = round(time.time() - t0, 1)
    args.out.write_text(json.dumps(report, indent=2) + "\n")
    for pt in report["scaling"]:
        print(f"namenodes={pt['namenodes']:3d}  "
              f"throughput={pt['throughput_ops_s']:12,.1f} ops/s  "
              f"p99={pt['latency_p99_ms']:.1f} ms  "
              f"speedup={pt['speedup_vs_min_nn']}x")
    f = report["functional_batching"]
    print(f"functional: {f['round_trip_savings_pct']}% round-trip savings "
          f"(reactive), {f['planned_savings_pct']}% (planned; "
          f"{f['planned_vs_reactive_savings_pct']}% vs reactive), "
          f"state_matches_sequential={f['state_matches_sequential']}")
    lf = f["local_rt_fraction"]
    print(f"local RT share: seq {lf['sequential']} -> reactive "
          f"{lf['reactive']} -> planned {lf['planned']}; batched writes "
          f"{f['batched_write_fraction']}")
    w = report["functional_batching_write_heavy"]
    print(f"write-heavy mix: batched writes {w['batched_write_fraction']} "
          f"(lease-ordered {w['planner']['lease_ordered_ops']} ops), "
          f"planned {w['planned_vs_reactive_savings_pct']}% fewer RTs vs "
          f"reactive, state_matches_sequential="
          f"{w['state_matches_sequential']}")
    wc = w["planned_concurrent"]
    print(f"planned+concurrent (write-heavy): batched writes "
          f"{wc['batched_write_fraction']} "
          f"(deterministic {w['batched_write_fraction']}), "
          f"{wc['vs_reactive_savings_pct']}% fewer RTs vs reactive, "
          f"client hint hit rate {wc['hint_cache']['hit_rate']} "
          f"(stale {wc['hint_cache']['stale_overwrites']})")
    hc = f["hint_cache"]
    print(f"closed loop (spotify): client hint hit rate {hc['hit_rate']}, "
          f"windows {f['planner']['window_sizes']}")
    fo = report["failover"]
    print(f"failover: killed NN {fo['killed_namenode']}/"
          f"{fo['n_namenodes']} at {fo['kill_at_s']} s -> dip "
          f"{fo['dip_depth_pct']}% of steady, recovered in "
          f"{fo['recovery_s']} s ({fo['ops_to_recovery']} ops), "
          f"{fo['zero_bins_after_kill']} zero bins after kill")
    el = report["elasticity"]
    print(f"elasticity: {el['n_namenodes_base']}->"
          f"{el['n_namenodes_peak']}->{el['n_namenodes_base']} NN, "
          f"+{el['scale_out_gain_pct']}% during scale-out "
          f"({el['zero_bins_during_scale_out']} zero bins), scale-in "
          f"settled in {el['scale_in_recovery_s']} s; pool "
          f"{el['pool_scale_outs']} out/{el['pool_scale_ins']} in, "
          f"hint hit rate {el['hint_hit_rate_before']} -> "
          f"{el['hint_hit_rate_after']} "
          f"({el['migrated_hint_entries']} entries migrated), "
          f"state_matches_sequential={el['state_matches_sequential']}")
    ov = report["overload"]
    print(f"overload: 1 gray-slow NN of {ov['n_namenodes']}, goodput "
          f"{ov['unprotected']['goodput_frac']} -> "
          f"{ov['protected']['goodput_frac']} protected "
          f"(+{ov['goodput_gain_pct']}%), late completions "
          f"{ov['unprotected']['late_completions']} -> "
          f"{ov['protected']['late_completions']}, worst tenant p99 "
          f"{ov['unprotected']['worst_tenant_p99_ticks']} -> "
          f"{ov['protected']['worst_tenant_p99_ticks']} ticks, "
          f"{ov['breaker_trips']} breaker trips, "
          f"state_matches_sequential={ov['state_matches_sequential']}")
    co = report["columnar"]
    print(f"columnar: {co['hintchain_launches']} hintchain + "
          f"{co['pkval_launches']} pkval launches over {co['ops']} ops "
          f"({co['launches_per_op']} launches/op), "
          f"{co['pkval_probes']} PK probes ({co['pkval_demotions']} "
          f"demoted), wall {co['wall_s_dict']} s dict -> "
          f"{co['wall_s_columnar']} s columnar, "
          f"state_matches_oracle={co['state_matches_oracle']}")
    _print_big_dir(report["big_dir"])
    print(f"wrote {args.out}")


def _print_big_dir(bd: Dict) -> None:
    print(f"big_dir: paced delete of {bd['total_inodes']:,} inodes in "
          f"{bd['chunks']} chunks ({bd['waves']} waves, peak frontier "
          f"{bd['peak_frontier']:,}), wall {bd['subtree_wall_s_dict']} s "
          f"dict / {bd['subtree_wall_s_columnar']} s columnar; adjacent "
          f"p99 {bd['baseline_p99_ms']} -> {bd['paced_p99_ms']} ms "
          f"({bd['p99_ratio']}x), {bd['treeagg_launches']} treeagg "
          f"launches ({bd['treeagg_demotions']} demoted), "
          f"state_matches_oracle={bd['state_matches_oracle']}, "
          f"incremental_matches_legacy={bd['incremental_matches_legacy']}")


if __name__ == "__main__":
    main()
