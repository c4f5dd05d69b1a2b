"""Batched multi-namenode request pipeline (paper §2.2, §7.2).

The two contract properties from the issue:
  1. batched execution leaves the store in EXACTLY the state sequential
     execution does (strict full-table equality on a single namenode;
     logical-namespace equality across namenode counts, where physical
     ids legitimately differ);
  2. OpCost accounting is conserved across batching: the merge of per-
     namenode aggregates == the pipeline's total == the merge of every
     successful op's cost.
Plus: the vectorized phash partition grouping agrees with the store's
partitioner, batching actually saves round trips, the batched DES scales
with namenode count, and the trace generator matches the §7.2 mix.
"""

from repro.core import (BatchPlanner, MetadataStore, NamenodeCluster,
                        OpCost, PlannedRequestPipeline, RequestPipeline,
                        WorkloadOp, format_fs, materialize_namespace,
                        namespace_snapshot)
from repro.core.cluster_sim import BatchedHopsFSSim, profile_ops
from repro.core.store import _hash_key
from repro.core.tables import ROOT_ID, make_inode
from repro.core.workload import (NamespaceSpec, SPOTIFY_TRACE_MIX,
                                 SpotifyWorkload, SyntheticNamespace,
                                 TraceReplay, make_spotify_trace)


def _build(n_namenodes: int, *, n_dirs: int = 16, files_per_dir: int = 4):
    store = MetadataStore(n_datanodes=4)
    format_fs(store)
    cluster = NamenodeCluster(store, n_namenodes)
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=n_dirs,
                            files_per_dir=files_per_dir)
    materialize_namespace(cluster.namenodes[0], ns)
    return store, cluster, ns


def _trace(ns, n_ops=300, seed=5):
    return make_spotify_trace(ns, n_ops, seed=seed)


# ---------------------------------------------------------------------------
# 1. state equivalence
# ---------------------------------------------------------------------------

def test_batched_equals_sequential_state_single_nn():
    """Strict equality: with one namenode, batched execution must leave
    every table byte-identical to sequential execution (same mtimes, same
    ids — nothing may be reordered observably)."""
    ns_ref = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    trace = _trace(ns_ref)
    store_seq, cluster_seq, _ = _build(1)
    seq = RequestPipeline(cluster_seq, batch_size=1).run(trace)
    store_bat, cluster_bat, _ = _build(1)
    bat = RequestPipeline(cluster_bat, batch_size=8).run(trace)
    assert store_seq.dump_state() == store_bat.dump_state()
    # same per-op outcome stream too
    assert [(o.ok, o.error) for o in seq.outcomes] == \
           [(o.ok, o.error) for o in bat.outcomes]
    assert bat.batched_fraction > 0.2     # batching actually engaged


def test_batched_equals_sequential_namespace_multi_nn():
    """Across namenode counts the physical ids differ (per-NN id-allocator
    blocks) but the logical namespace must be identical."""
    ns_ref = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    trace = _trace(ns_ref)
    store_seq, cluster_seq, _ = _build(1)
    RequestPipeline(cluster_seq, batch_size=1).run(trace)
    store_bat, cluster_bat, _ = _build(4)
    RequestPipeline(cluster_bat, batch_size=8).run(trace)
    assert namespace_snapshot(store_seq) == namespace_snapshot(store_bat)


# ---------------------------------------------------------------------------
# 2. cost conservation
# ---------------------------------------------------------------------------

def test_opcost_conserved_across_batching():
    _, cluster, ns = _build(4)
    stats = RequestPipeline(cluster, batch_size=8).run(_trace(ns))
    per_nn = OpCost()
    for c in stats.per_nn_cost.values():
        per_nn.merge(c)
    per_op = OpCost()
    for o in stats.outcomes:
        if o.ok:
            per_op.merge(o.result.cost)
    assert per_nn.as_dict() == stats.total_cost.as_dict() == per_op.as_dict()
    # every op got an outcome, and namenode op counters agree
    assert stats.ok + stats.failed == len(stats.outcomes)
    assert sum(stats.per_nn_ops.values()) == stats.ok


def test_batching_saves_round_trips():
    ns_ref = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    trace = _trace(ns_ref)
    _, cluster_seq, _ = _build(1)
    seq = RequestPipeline(cluster_seq, batch_size=1).run(trace)
    _, cluster_bat, _ = _build(1)
    bat = RequestPipeline(cluster_bat, batch_size=16).run(trace)
    assert bat.total_cost.round_trips < seq.total_cost.round_trips
    # reads dominate the §7.2 mix => savings should be substantial
    assert bat.total_cost.round_trips <= 0.95 * seq.total_cost.round_trips


def test_concurrent_pipeline_row_lock_contention():
    """Threaded namenodes hammering the SAME rows: a mixed read/write
    trace where every mutation targets one of a handful of files (target
    row X locks), one shared directory (parent mtime + quota row), and
    one lease holder. No op may be lost, and OpCost accounting must stay
    conserved under real row-lock contention."""
    store, cluster, _ = _build(4, n_dirs=4, files_per_dir=4)
    nn0 = cluster.namenodes[0]
    hot_dir = "/w/hot"
    nn0.ops.mkdirs(hot_dir)
    hot = [f"{hot_dir}/h{i}" for i in range(6)]
    for p in hot:
        nn0.ops.create(p)
    wops = []
    for i in range(240):
        k = i % 6
        if i % 4 == 0:
            wops.append(WorkloadOp("chmod_file", hot[k],
                                   args={"perm": 0o600 + (i % 8)}))
        elif i % 4 == 1:
            wops.append(WorkloadOp("read", hot[k]))
        elif i % 4 == 2:
            wops.append(WorkloadOp("set_replication", hot[k],
                                   args={"repl": 1 + (i % 3)}))
        else:
            wops.append(WorkloadOp("create", f"{hot_dir}/new{i:04d}"))
    stats = RequestPipeline(cluster, batch_size=8,
                            concurrent=True).run(wops)
    # nothing lost: every op got exactly one outcome
    assert stats.ok + stats.failed == len(wops)
    assert all(o is not None for o in stats.outcomes)
    # the overwhelming majority must succeed (row-lock waits block, they
    # don't fail; only a >1.2s stall would surface as LockTimeout)
    assert stats.ok >= 0.95 * len(wops)
    # conserved accounting under contention
    per_nn = OpCost()
    for c in stats.per_nn_cost.values():
        per_nn.merge(c)
    per_op = OpCost()
    for o in stats.outcomes:
        if o.ok:
            per_op.merge(o.result.cost)
    assert per_nn.as_dict() == stats.total_cost.as_dict() \
        == per_op.as_dict()
    assert sum(stats.per_nn_ops.values()) == stats.ok
    # every create landed exactly once
    snap = namespace_snapshot(store)
    assert all(f"{hot_dir}/new{i:04d}" in snap
               for i in range(3, 240, 4))


def test_concurrent_pipeline_namespace_consistent():
    """Threaded namenodes over the shared store: every op completes and
    the namespace matches a sequential run of the same trace (the trace's
    mutations target distinct paths, so interleaving is benign)."""
    ns_ref = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    trace = _trace(ns_ref, n_ops=200)
    store_seq, cluster_seq, _ = _build(1)
    RequestPipeline(cluster_seq, batch_size=1).run(trace)
    store_con, cluster_con, _ = _build(4)
    stats = RequestPipeline(cluster_con, batch_size=8,
                            concurrent=True).run(trace)
    assert stats.ok + stats.failed == len(trace)
    assert namespace_snapshot(store_con) == namespace_snapshot(store_seq)


# ---------------------------------------------------------------------------
# 2b. grouped WRITE path (create/mkdirs/setattr sharing one transaction)
# ---------------------------------------------------------------------------

def _single_nn():
    store = MetadataStore(n_datanodes=4)
    format_fs(store)
    cluster = NamenodeCluster(store, 1)
    nn = cluster.namenodes[0]
    nn.ops.mkdirs("/a/b")
    nn.ops.mkdirs("/a/c")
    return store, nn


def test_grouped_writes_equal_sequential_state():
    """Runs of creates/mkdirs/setattrs share one transaction; ids, mtimes
    and every row must still be byte-identical to sequential execution
    (execute phases run in submission order inside the group)."""
    wops = ([WorkloadOp("create", f"/a/b/f{i}") for i in range(6)]
            + [WorkloadOp("create", "/a/c/g0"),
               WorkloadOp("create", "/a/b/f0")]          # in-group dup
            + [WorkloadOp("mkdirs", f"/a/c/d{i}") for i in range(4)]
            + [WorkloadOp("chmod_file", f"/a/b/f{i}",
                          args={"perm": 0o600}) for i in range(4)])
    store_b, nn_b = _single_nn()
    out_b = nn_b.execute_batch(wops)
    store_s, nn_s = _single_nn()
    out_s = [nn_s._safe_exec(w) for w in wops]
    assert store_b.dump_state() == store_s.dump_state()
    assert [(o.ok, o.error) for o in out_b] == \
           [(o.ok, o.error) for o in out_s]
    # the grouped write path actually engaged, including the dup error
    assert nn_b.batched_write_ops >= 10
    assert [o.error for o in out_b].count("FileAlreadyExists") == 1
    # conserved accounting
    agg = OpCost()
    for o in out_b:
        if o.ok:
            agg.merge(o.result.cost)
    assert agg.as_dict() == nn_b.agg_cost.as_dict()


def test_grouped_writes_save_round_trips():
    """A run of creates through the grouped path costs fewer round trips
    than the same creates executed sequentially."""
    wops = [WorkloadOp("create", f"/a/b/n{i}") for i in range(8)]
    store_b, nn_b = _single_nn()
    for o in nn_b.execute_batch(wops):
        assert o.ok and o.batched
    store_s, nn_s = _single_nn()
    for w in wops:
        assert nn_s._safe_exec(w).ok
    # agg_cost only counts pipeline-served ops (the _single_nn warmup goes
    # through HopsFSOps directly), so this compares exactly the two runs
    assert nn_b.agg_cost.round_trips < nn_s.agg_cost.round_trips


# ---------------------------------------------------------------------------
# 2c. planned mode: client-side columnar batch planner
# ---------------------------------------------------------------------------

def test_planned_pipeline_equivalence_and_savings():
    """The ISSUE acceptance bar, on the quick-mode Spotify trace at 4
    namenodes: planner mode cuts total DB round trips >= 20% vs the
    reactive pipeline, the batched fraction (reads+writes) strictly
    exceeds the read-only batched fraction, the local round-trip share
    rises, and planned/reactive/sequential execution all converge to the
    same logical namespace."""
    ns_ref = SyntheticNamespace(NamespaceSpec(), n_dirs=20, files_per_dir=4)
    trace = make_spotify_trace(ns_ref, 600, seed=5)

    def build():
        store = MetadataStore(n_datanodes=4)
        format_fs(store)
        cluster = NamenodeCluster(store, 4)
        ns = SyntheticNamespace(NamespaceSpec(), n_dirs=20,
                                files_per_dir=4)
        materialize_namespace(cluster.namenodes[0], ns)
        return store, cluster

    store_seq, cl = build()
    seq = RequestPipeline(cl, batch_size=1).run(trace)
    store_rea, cl = build()
    rea = RequestPipeline(cl, batch_size=16).run(trace)
    store_pln, cl = build()
    pipe = PlannedRequestPipeline(cl, batch_size=16)
    pln = pipe.run(trace)
    # every op accounted for, nothing spuriously failed by planning
    assert pln.ok + pln.failed == len(trace)
    assert pln.failed <= seq.failed
    # >= 20% fewer DB round trips than the reactive pipeline (measured
    # ~40%; the bar leaves headroom for mix drift)
    assert pln.total_cost.round_trips <= 0.8 * rea.total_cost.round_trips
    # grouped writes engaged: total batched share strictly above read-only
    assert pln.batched_write_fraction > 0
    assert pln.batched_fraction > pln.batched_read_fraction
    assert pln.batched_fraction > rea.batched_fraction
    # DAT alignment: local round-trip share rises under the planner
    assert pln.local_rt_fraction > rea.local_rt_fraction
    assert pln.local_rt_fraction > seq.local_rt_fraction
    # final-state equivalence across all three execution modes
    snap = namespace_snapshot(store_seq)
    assert snap == namespace_snapshot(store_rea)
    assert snap == namespace_snapshot(store_pln)
    # planner telemetry: client-side resolutions + fused kernel ran
    rep = pipe.plan_report
    assert rep is not None and rep.planned_ops > 0
    assert rep.batches > 0 and rep.windows > 0


def test_planned_pipeline_cost_conserved():
    ns_ref = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    trace = make_spotify_trace(ns_ref, 300, seed=7)
    store, cluster, ns = _build(4)
    stats = PlannedRequestPipeline(cluster, batch_size=8).run(trace)
    per_nn = OpCost()
    for c in stats.per_nn_cost.values():
        per_nn.merge(c)
    per_op = OpCost()
    for o in stats.outcomes:
        if o.ok:
            per_op.merge(o.result.cost)
    assert per_nn.as_dict() == stats.total_cost.as_dict() \
        == per_op.as_dict()
    assert stats.ok + stats.failed == len(stats.outcomes)
    del store, ns


def test_planned_concurrent_namespace_consistent():
    ns_ref = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    trace = make_spotify_trace(ns_ref, 200, seed=5)
    store_seq, cluster_seq, _ = _build(1)
    RequestPipeline(cluster_seq, batch_size=1).run(trace)
    store_con, cluster_con, _ = _build(4)
    stats = PlannedRequestPipeline(cluster_con, batch_size=8,
                                   concurrent=True).run(trace)
    assert stats.ok + stats.failed == len(trace)
    assert namespace_snapshot(store_con) == namespace_snapshot(store_seq)


def test_planner_orders_unresolved_ops():
    """A read of a path created earlier in the same window cannot resolve
    client-side, so it is pinned to submission order — it must never be
    dealt ahead of the create and spuriously fail."""
    _store, cluster, _ = _build(2)
    trace = []
    for i in range(30):
        p = f"/w/newfile{i:02d}"
        trace.append(WorkloadOp("create", p))
        trace.append(WorkloadOp("read", p))
    stats = PlannedRequestPipeline(cluster, batch_size=8).run(trace)
    assert stats.failed == 0
    assert stats.ok == len(trace)


def test_planner_pins_conflicting_mutations():
    """Destructive ops, duplicate mutation paths, and prefix-related
    mutations are pinned (kept in submission order); independent creates
    and all reads stay free for partition-aligned dealing."""
    _store, cluster, _ = _build(2)
    planner = BatchPlanner(cluster, batch_size=4)
    wops = [
        WorkloadOp("read", "/w/f0000.parquet"),            # 0 free
        WorkloadOp("create", "/w/x1"),                     # 1 free
        WorkloadOp("create", "/w/x2"),                     # 2 free
        WorkloadOp("delete_file", "/w/f0001.parquet"),     # 3 destructive
        WorkloadOp("mkdirs", "/w/sub/leaf"),               # 4 prefix of 5
        WorkloadOp("chmod_file", "/w/sub",
                   args={"perm": 0o700}),                  # 5 prefix of 4
        WorkloadOp("create", "/w/dup"),                    # 6 dup with 7
        WorkloadOp("create", "/w/dup"),                    # 7 dup with 6
    ]
    batches = planner.plan(wops)
    pinned = {i for b in batches if b.ordered for i in b.indices}
    assert pinned == {3, 4, 5, 6, 7}
    # pinned batches preserve submission order
    ordered = [i for b in batches if b.ordered for i in b.indices]
    assert ordered == sorted(ordered)
    # every op dealt exactly once
    dealt = sorted(i for b in batches for i in b.indices)
    assert dealt == list(range(len(wops)))


# ---------------------------------------------------------------------------
# 3. vectorized partition grouping (phash kernel path)
# ---------------------------------------------------------------------------

def test_vectorized_partitions_match_store():
    from repro.core.namenode import _partitions_for
    store = MetadataStore(n_datanodes=4)
    ids = [1, 2, 3, 999, 12345, 2**31 - 1, 64, 65]
    expect = [store.table("inode").partition_of(i) for i in ids]
    # scalar path (small batch) and forced kernel path must both agree
    assert _partitions_for(ids, store.n_partitions) == expect
    assert _partitions_for(ids, store.n_partitions, min_batch=1) == expect
    assert expect == [_hash_key(i) % store.n_partitions for i in ids]


def test_phash_fallback_recovers_after_transient_failure(monkeypatch):
    """A transient kernel failure must not latch the scalar fallback
    forever: the probe re-enables the vectorized path after a bounded
    number of calls (the old module-global bool stayed False for the
    process lifetime)."""
    import repro.kernels.phash.ops as phash_ops
    from repro.core import namenode as nn_mod
    probe = nn_mod._KernelProbe("phash", reprobe_every=3)
    monkeypatch.setattr(nn_mod, "_phash_probe", probe)
    calls = {"kernel": 0, "fail_next": 1}
    real = phash_ops.phash_partitions

    def flaky(ids, n_partitions, **kw):
        calls["kernel"] += 1
        if calls["fail_next"] > 0:
            calls["fail_next"] -= 1
            raise RuntimeError("transient accelerator failure")
        return real(ids, n_partitions, **kw)

    monkeypatch.setattr(phash_ops, "phash_partitions", flaky)
    store = MetadataStore(n_datanodes=4)
    ids = list(range(40))
    expect = [_hash_key(i) % store.n_partitions for i in ids]
    # 1st call: kernel raises, scalar fallback still returns right answer
    assert nn_mod._partitions_for(ids, store.n_partitions,
                                  min_batch=1) == expect
    assert probe.failures == 1
    # next calls fall back WITHOUT touching the kernel (bounded backoff)
    for _ in range(2):
        assert nn_mod._partitions_for(ids, store.n_partitions,
                                      min_batch=1) == expect
    assert calls["kernel"] == 1
    # ...then the re-probe fires, the kernel works again, and the
    # vectorized path stays enabled
    assert nn_mod._partitions_for(ids, store.n_partitions,
                                  min_batch=1) == expect
    assert calls["kernel"] == 2 and probe.failures == 0
    assert nn_mod._partitions_for(ids, store.n_partitions,
                                  min_batch=1) == expect
    assert calls["kernel"] == 3


def test_namespace_snapshot_deep_namespace():
    """path_of is iterative: a namespace deeper than Python's recursion
    limit (~1000) must still snapshot completely."""
    depth = 2200
    store = MetadataStore(n_datanodes=4)
    format_fs(store)
    t = store.table("inode")
    parent = ROOT_ID
    for i in range(depth):
        iid = 10 + i
        t.put(make_inode(iid, parent, f"d{i}", True))
        parent = iid
    snap = namespace_snapshot(store)
    assert len(snap) == depth
    deepest = "/" + "/".join(f"d{i}" for i in range(depth))
    assert deepest in snap


# ---------------------------------------------------------------------------
# 4. trace generation + DES scaling
# ---------------------------------------------------------------------------

def test_spotify_trace_mix():
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=30)
    wl = SpotifyWorkload(ns, seed=3, mix=SPOTIFY_TRACE_MIX)
    hist = wl.mix_histogram(20_000)
    assert 64.0 < hist.get("read", 0) < 70.0          # ~67% getBlockLocations
    assert 10.0 < hist.get("ls", 0) < 14.0            # ~12% listStatus


def test_trace_replay_deterministic():
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=10)
    trace = make_spotify_trace(ns, 50, seed=9)
    r1, r2 = TraceReplay(trace), TraceReplay(trace)
    a = [r1.next_op() for _ in range(120)]
    b = [r2.next_op() for _ in range(120)]
    assert a == b
    assert a[:50] == trace and a[50:100] == trace      # cyclic


def test_batched_sim_throughput_scales_with_namenodes():
    profiles = profile_ops()
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=30)
    trace = make_spotify_trace(ns, 1000, seed=11)
    tps = []
    for n_nn in (1, 4):
        sim = BatchedHopsFSSim(n_namenodes=n_nn, n_ndb=8,
                               profiles=profiles, batch_size=16, seed=1)
        sim.start_clients(150 * n_nn, TraceReplay(trace))
        tps.append(sim.run(0.15).throughput)
    assert tps[1] > 2.0 * tps[0]


def test_batched_sim_planned_mode_batches_more():
    """The DES mirror of the planner: partition-aligned, type-pure batch
    pulls collapse far more validation exchanges than FIFO slices."""
    profiles = profile_ops()
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=30)
    trace = make_spotify_trace(ns, 1000, seed=11)
    stats = {}
    for planned in (False, True):
        sim = BatchedHopsFSSim(n_namenodes=4, n_ndb=8, profiles=profiles,
                               batch_size=16, seed=1, planned=planned)
        sim.start_clients(600, TraceReplay(trace))
        res = sim.run(0.15)
        stats[planned] = (res.completed, sim.batched_ops, res.throughput)
    assert stats[True][0] > 0
    # planned pulls batch a much larger share of the completed ops
    assert stats[True][1] / stats[True][0] > \
        1.5 * stats[False][1] / stats[False][0]
    # and throughput does not regress
    assert stats[True][2] >= 0.95 * stats[False][2]


def test_batched_sim_batching_engages_under_load():
    profiles = profile_ops()
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=30)
    trace = make_spotify_trace(ns, 1000, seed=11)
    sim = BatchedHopsFSSim(n_namenodes=1, n_ndb=4, profiles=profiles,
                           batch_size=16, seed=1)
    sim.start_clients(400, TraceReplay(trace))
    res = sim.run(0.15)
    assert res.completed > 0
    assert sim.batched_ops > 0.2 * res.completed
    # nn-side counter ticks at batch finish; client-side `completed` half an
    # RTT later, so in-flight ops at the horizon leave nn counters ahead
    assert sum(sim.nn_ops_completed) >= res.completed
