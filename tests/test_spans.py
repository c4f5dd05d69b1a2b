"""Spans and counters at the program's layer boundaries: the bytes each
kernel launch hands to the device (``_KernelProbe.h2d_bytes``), the
per-op completion stamps (``OpOutcome.done_s``), and the spans a planned
run writes into a profiler trace (``repro.core.spans``)."""
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import DFSClient, NamenodeCluster, materialize_namespace
from repro.core import format_fs
from repro.core.columnar import (ColumnarMetadataStore, HashIndex,
                                 _pkval_probe, _validate_chains)
from repro.core.namenode import _KernelProbe, _with_phash_kernel
from repro.core.workload import (NamespaceSpec, SyntheticNamespace,
                                 make_spotify_trace, name_hash32)

U32 = I32 = 4          # bytes per element of the kernels' uint32/int32


def _phash():
    from repro.kernels.phash.ops import phash_partitions
    keys = np.arange(5, dtype=np.int64) * 7919
    return (lambda: phash_partitions(keys, 64)), 8 * U32


def _phash_chain():
    from repro.kernels.phash.ops import phash_chains
    n, d = 5, 3
    par = np.arange(n * d, dtype=np.int64).reshape(n, d) + 1
    nam = par * 31
    # 5 chains pad to 8; parents and names depth-major [3, 8], hints and
    # depths [1, 8]
    return (lambda: phash_chains(par, nam, np.arange(n) + 2,
                                 np.full(n, d), 64),
            2 * d * 8 * U32 + 8 * U32 + 8 * I32)


def _pkval():
    from repro.kernels.pkval.ops import pkval_lookup
    idx = HashIndex()
    for i in range(10):
        idx.set(1, name_hash32(f"f{i}"), 100 + i)
    tp, tn, tv = idx.arrays()
    parents = np.ones(5, np.int64)
    names = np.array([name_hash32(f"f{i}") for i in range(5)], np.int64)
    # the whole index (3 arrays of cap slots), then 5 probes padded to 8
    return (lambda: pkval_lookup(tp, tn, tv, parents, names),
            3 * idx.cap * I32 + 8 * I32 + 8 * U32)


def _hintchain():
    from repro.kernels.hintchain.ops import hintchain_resolve
    cidx, fidx = HashIndex(), HashIndex(cap=128)
    cidx.set(1, name_hash32("a"), 2)
    fidx.set(2, name_hash32("b"), 3)
    nam = np.array([[name_hash32("a"), name_hash32("b"), 0, 0]] * 5)
    dep = np.full(5, 2)
    return (lambda: hintchain_resolve(cidx.arrays(), fidx.arrays(), nam,
                                      dep, root_id=1),
            3 * (64 + 128) * I32 + 8 * 4 * U32 + 8 * I32)


def _treeagg():
    from repro.kernels.treeagg.ops import treeagg_expand
    par = np.arange(100, dtype=np.int64) % 7
    ones = np.ones(100, np.int64)
    # 3 wave members pad to 8; 100 slots pad to 128, three columns
    return (lambda: treeagg_expand(np.array([1, 2, 3]), par, ones, ones),
            8 * I32 + 3 * 128 * I32)


@pytest.mark.parametrize("family,case", [
    ("phash", _phash), ("phash_chain", _phash_chain), ("pkval", _pkval),
    ("hintchain", _hintchain), ("treeagg", _treeagg)])
def test_h2d_bytes_are_the_padded_arrays_handed_over(family, case):
    kern, want = case()
    probe = _KernelProbe(family)
    _, used = _with_phash_kernel(kern, lambda: None, n_keys=8, min_batch=2,
                                 probe=probe)
    assert used and probe.launches == 1
    assert probe.h2d_bytes == want
    _with_phash_kernel(kern, lambda: None, n_keys=8, min_batch=2,
                       probe=probe)
    assert probe.h2d_bytes == 2 * want
    # below the gate nothing is launched and nothing is counted
    _with_phash_kernel(kern, lambda: None, n_keys=1, min_batch=2,
                       probe=probe)
    assert probe.h2d_bytes == 2 * want


def test_pkval_launch_ships_the_whole_index():
    store = ColumnarMetadataStore(n_datanodes=4)
    format_fs(store)
    cluster = NamenodeCluster(store, 1)
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    materialize_namespace(cluster.namenodes[0], ns)
    hindex = store.table("inode").hindex
    rows = [r for part in store.table("inode").parts
            for r in part.values() if r["name"]][:40]
    chains = [(((r["parent_id"], r["name"]),), r["id"]) for r in rows]
    before = _pkval_probe.h2d_bytes
    demoted, probes, used = _validate_chains(hindex, chains, min_batch=2)
    assert used and probes == len(chains) and not demoted
    assert _pkval_probe.h2d_bytes - before >= 3 * hindex.cap * 4


@pytest.fixture
def planned_cluster(monkeypatch):
    """A 2-namenode columnar cluster on a small namespace, with the
    kernels' size gates lowered so a 256-op window launches them."""
    from repro.core import columnar
    monkeypatch.setattr(columnar, "HINTCHAIN_MIN_BATCH", 16)
    monkeypatch.setattr(columnar, "PKVAL_MIN_BATCH", 16)
    store = ColumnarMetadataStore(n_datanodes=4)
    format_fs(store)
    cluster = NamenodeCluster(store, 2)
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    materialize_namespace(cluster.namenodes[0], ns)
    return cluster, make_spotify_trace(ns, 600, seed=5)


def test_done_stamps_follow_batch_order(planned_cluster):
    cluster, wops = planned_cluster
    order = []                      # trace indices, per executed batch
    index_of = {id(w): i for i, w in enumerate(wops)}
    for nn in cluster.namenodes:
        run = nn.execute_batch

        def recorded(batch, *a, _run=run, **kw):
            order.append([index_of[id(w)] for w in batch])
            return _run(batch, *a, **kw)
        nn.execute_batch = recorded
    client = DFSClient(cluster)
    t0 = time.perf_counter()
    st = client.run_trace(wops, planned=True, window=256, adaptive=False)
    returned = time.perf_counter()
    assert len(order) > 4 and sum(map(len, order)) == len(wops)
    last = t0
    for batch in order:
        stamps = {st.outcomes[i].done_s for i in batch}
        assert len(stamps) == 1            # one clock read per batch
        (done,) = stamps
        assert last <= done <= returned
        last = done


def test_profiled_run_writes_program_spans(planned_cluster, tmp_path):
    from jax.profiler import ProfileData
    cluster, wops = planned_cluster
    client = DFSClient(cluster)
    with jax.profiler.trace(str(tmp_path)):
        client.run_trace(wops, planned=True, window=256, adaptive=False)
    (pb,) = Path(tmp_path).rglob("*.xplane.pb")
    names, windows = set(), set()
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "planner.window":
                    windows.add(dict(ev.stats)["window"])
    assert {"planner.window", "planner.lower", "planner.snapshot",
            "planner.validate", "planner.deal", "planner.absorb",
            "namenode.batch", "namenode.single",
            "client.finalize"} <= names
    assert {"kernel.hintchain", "kernel.pkval"} <= names
    assert len(windows) == 3               # 600 ops in windows of 256


# ---------------------------------------------------------------------------
# the hint-cache snapshots' upkeep: counters and the span's metadata
# ---------------------------------------------------------------------------


def test_snapshot_counters_count_rebuilds_and_dirty_keys():
    from repro.core.batch_planner import HintResolver, MultiCacheResolver
    from repro.core.columnar import lower_trace_fused
    from repro.core.hint_cache import InodeHintCache
    client = InodeHintCache(capacity=3)
    nn0, nn1 = InodeHintCache(), InodeHintCache()
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=4, files_per_dir=2)
    wops = make_spotify_trace(ns, 40, seed=1)
    r = HintResolver(client, MultiCacheResolver([nn0, nn1]))

    def counts():
        _, used = lower_trace_fused(wops, r, min_batch=2)
        assert used
        return r.snapshot_rebuilds, r.snapshot_delta_keys

    assert counts() == (2, 0)              # one full build per view
    client.put(1, "a", 5)
    client.put(1, "b", 6)
    client.put(1, "c", 7)
    nn0.put(1, "a", 5)
    nn0.put(1, "d", 8)
    nn1.put(1, "a", 5)                     # dirty in two caches: one key
    assert counts() == (2, 5)
    client.put(1, "a", 5)                  # the same id again: nothing
    nn1.put(1, "a", 5)
    assert counts() == (2, 5)
    client.put(1, "a", 9)                  # another id
    client.invalidate(1, "b")
    assert counts() == (2, 7)
    client.put(1, "e", 10)
    client.put(1, "f", 11)                 # evicts (1, "c")
    assert counts() == (2, 10)
    for name in "ghij":                    # more keys than the cache holds
        client.put(1, name, 20)
    assert counts() == (3, 10)             # a full journal rebuilds
    client.clear()
    assert counts() == (4, 10)             # so does a clear
    r.fallback = MultiCacheResolver([nn1, nn0])
    assert counts() == (5, 10)             # and a membership change
    nn0.clear()
    nn1.put(2, "k", 12)
    assert counts() == (6, 10)             # the rebuild covers nn1's key


def test_unobserved_cache_records_nothing():
    from repro.core.hint_cache import InodeHintCache
    cache = InodeHintCache(capacity=2)
    for i in range(4):                     # puts and evictions
        cache.put(1, f"n{i}", 10 + i)
    cache.invalidate(1, "n3")
    cache.clear()
    assert len(cache._journals) == 0
    j = cache.attach_journal()
    cache.put(1, "x", 3)
    cache.detach_journal(j)
    cache.put(1, "y", 4)
    cache.clear()
    assert cache.drain_journal(j) == (False, {(1, "x")})
    assert len(cache._journals) == 0


def test_only_planned_runs_attach_journals(planned_cluster):
    cluster, wops = planned_cluster
    client = DFSClient(cluster)
    client.mkdirs("/facade/dir")
    client.stat("/facade/dir")
    caches = [client.hint_cache] + [nn.ops.cache for nn in cluster.namenodes]
    assert all(len(c._journals) == 0 for c in caches)
    client.run_trace(wops, planned=True, window=256, adaptive=False)
    assert all(len(c._journals) == 1 for c in caches)


def test_snapshot_span_carries_the_counters(planned_cluster, tmp_path):
    from jax.profiler import ProfileData
    from repro.core.batch_planner import PlannedRequestPipeline
    from repro.core.hint_cache import InodeHintCache
    cluster, wops = planned_cluster
    cache = InodeHintCache()
    reports, meta = [], []
    with jax.profiler.trace(str(tmp_path)):
        for part in (wops[:300], wops[300:]):
            pipe = PlannedRequestPipeline(cluster, window=150,
                                          adaptive=False, client_cache=cache)
            pipe.run(part)
            reports.append(pipe.plan_report)
    (pb,) = Path(tmp_path).rglob("*.xplane.pb")
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "planner.snapshot":
                    st = dict(ev.stats)
                    meta.append((st["snapshot_rebuilds"],
                                 st["snapshot_delta_keys"]))
    assert len(meta) == sum(r.hintchain_launches for r in reports) == 4
    # the first window builds both views; later ones, pipelines included,
    # apply what changed
    assert meta[0] == (2, 0) and all(m[0] == 0 for m in meta[1:])
    assert sum(m[1] for m in meta[1:]) > 0
    assert [r.snapshot_rebuilds for r in reports] == [2, 0]
    assert sum(r.snapshot_delta_keys for r in reports) \
        == sum(m[1] for m in meta)
