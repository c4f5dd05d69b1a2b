"""The planner's persistent hint-cache snapshots (``columnar.HintSnapshot``),
brought up to date from the caches' change journals, against fresh
``HashIndex.from_entries`` snapshots of the same contents: key by key,
through the ``pkval`` and ``hintchain`` oracles, and window by window
through planned runs, where the rebuild path is kept as the oracle."""
import gc
import random
import sys
import threading
import weakref

import numpy as np
import pytest

import repro.core.batch_planner as batch_planner
import repro.core.columnar as columnar
from repro.core import NamenodeCluster, format_fs, materialize_namespace
from repro.core.batch_planner import (HintResolver, MultiCacheResolver,
                                      PlannedRequestPipeline)
from repro.core.columnar import (AMBIG, SNAPSHOT_SLOTS_PER_ENTRY,
                                 ColumnarMetadataStore, HashIndex,
                                 HintSnapshot, lower_trace_fused)
from repro.core.hint_cache import EPOCH_TAG, InodeHintCache
from repro.core.tables import ROOT_ID
from repro.core.workload import (NamespaceSpec, SyntheticNamespace,
                                 make_spotify_trace, name_hash32)
from repro.kernels.hintchain.ref import hintchain_ref
from repro.kernels.pkval.ref import pkval_ref

#: pairs of names with one crc32 (found by a seeded search)
COLLIDING = [("0xyf3rpz5c", "ga87t0m5rv"), ("s7es8sg4pi", "4qmt6x6i4e"),
             ("5yhe75m6bz", "ban3m5ajw6")]
NAMES = ["a", "b", "c", "d"] + [n for pair in COLLIDING for n in pair]


def test_colliding_names_share_a_hash():
    for a, b in COLLIDING:
        assert a != b and name_hash32(a) == name_hash32(b)


def _fresh(caches):
    """What the per-window rebuild gave: the caches merged first cache
    wins, built with ``from_entries``."""
    merged = {}
    for c in caches:
        for par, name, iid in c.export_entries():
            merged.setdefault((par, name), iid)
    return HashIndex.from_entries(
        (par, name, iid) for (par, name), iid in merged.items())


class Tree:
    """A two-level namespace: ``(parent_id, name) -> id`` for the root's
    children and theirs, every name drawn from ``NAMES``."""

    def __init__(self):
        self.id_of = {}
        nxt = ROOT_ID + 1
        for name in NAMES:
            self.id_of[(ROOT_ID, name)] = nxt
            nxt += 1
        for top in NAMES:
            for name in NAMES:
                self.id_of[(self.id_of[(ROOT_ID, top)], name)] = nxt
                nxt += 1
        self.keys = sorted(self.id_of)
        # absent keys: unknown names, unknown parents
        self.absent = ([(ROOT_ID, f"x{i}") for i in range(4)]
                       + [(nxt + 7, n) for n in NAMES[:3]])

    def chains(self, rng, n):
        """``n`` random component chains of depth 1-3 (depth 3 always
        ends in a miss: the tree has two levels)."""
        out = []
        for _ in range(n):
            d = rng.randint(1, 3)
            out.append([rng.choice(NAMES + ["zz"]) for _ in range(d)])
        return out


def _mutate(rng, tree, cache):
    """One seeded cache operation of every kind the planner meets."""
    key = rng.choice(tree.keys)
    roll = rng.random()
    if roll < 0.40:
        cache.put(*key, tree.id_of[key])             # new or repeated put
    elif roll < 0.50:
        cache.put(*key, tree.id_of[key] + 10_000)    # a stale overwrite
    elif roll < 0.62:
        cache.invalidate(*key)
    elif roll < 0.72:
        top = rng.choice(NAMES)
        path = [top] if rng.random() < 0.5 else [top, rng.choice(NAMES)]
        cache.invalidate_path(path)
    elif roll < 0.76:
        cache.clear()
    elif roll < 0.80:
        # an epoch gap the invalidation log no longer covers: wholesale
        seen = cache.seen_epoch
        cache.observe_epoch([(EPOCH_TAG, "", seen + 5),
                             (EPOCH_TAG, "/a", seen + 3)])
    elif roll < 0.84:
        seen = cache.seen_epoch
        cache.observe_epoch([(EPOCH_TAG, "", seen + 1),
                             (EPOCH_TAG, "/" + rng.choice(NAMES),
                              seen + 1)])
    else:
        for k in rng.sample(tree.keys, 3):           # LRU evictions
            cache.put(*k, tree.id_of[k])


def _encode(chains, depth=4):
    """Component chains as the kernels' (name hashes [N, depth], depths)."""
    nam = np.zeros((len(chains), depth), np.uint32)
    dep = np.zeros(len(chains), np.int32)
    for i, comps in enumerate(chains):
        dep[i] = len(comps)
        nam[i, :len(comps)] = [name_hash32(c) for c in comps]
    return nam, dep


def _assert_same_answers(tree, chains, client, cidx, fidx, caches):
    fresh_c, fresh_f = _fresh([client]), _fresh(caches)
    probes = tree.keys + tree.absent
    for got, want in ((cidx, fresh_c), (fidx, fresh_f)):
        for par, name in probes:
            assert got.get(par, name_hash32(name)) \
                == want.get(par, name_hash32(name)), (par, name)
        par = np.array([p for p, _ in probes], np.int32)
        nam = np.array([name_hash32(n) for _, n in probes], np.uint32)
        assert (pkval_ref(*got.arrays(), par, nam)
                == pkval_ref(*want.arrays(), par, nam)).all()
    nam, dep = _encode(chains)
    got = hintchain_ref(*cidx.arrays(), *fidx.arrays(), nam, dep)
    want = hintchain_ref(*fresh_c.arrays(), *fresh_f.arrays(), nam, dep)
    assert (got[0] == want[0]).all() and (got[1] == want[1]).all()


@pytest.mark.parametrize("n_nn", [2, 3, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_persistent_views_match_fresh_snapshots(seed, n_nn):
    rng = random.Random(seed * 100 + n_nn)
    tree = Tree()
    client = InodeHintCache(capacity=24)
    nns = [InodeHintCache(capacity=32 + 8 * k) for k in range(n_nn)]
    cview, fview = HintSnapshot(), HintSnapshot()
    members = list(nns)
    ambig_seen = 0
    for step in range(240):
        if step % 60 == 30:
            # membership: drop one, then reorder, then all back
            members = {30: nns[1:], 90: nns[::-1],
                       150: nns[:1] + nns[2:], 210: list(nns)}[step]
        _mutate(rng, tree, rng.choice([client] + nns))
        cview.refresh((client,))
        fview.refresh(members)
        _assert_same_answers(tree, tree.chains(rng, 12), client,
                             cview.index, fview.index, members)
        for view in (cview, fview):
            assert view.index.cap >= SNAPSHOT_SLOTS_PER_ENTRY \
                * view.index.live
        ambig_seen += int((fview.index.val == AMBIG).any())
    assert ambig_seen                     # collided buckets were exercised


def test_tombstones_compact_at_the_same_capacity():
    """Churn at a fixed live count: removals leave tombstones, and the
    snapshot rehashes them away instead of doubling."""
    cache = InodeHintCache()
    view = HintSnapshot()
    for i in range(20):
        cache.put(ROOT_ID, f"k{i}", 100 + i)
    view.refresh((cache,))
    start = view.index.cap
    for i in range(20, 600):
        cache.invalidate(ROOT_ID, f"k{i - 20}")
        cache.put(ROOT_ID, f"k{i}", 100 + i)
        view.refresh((cache,))
    idx = view.index
    assert idx.live == 20 and idx.cap == start
    fresh = _fresh([cache])
    for i in range(600):
        h = name_hash32(f"k{i}")
        assert idx.get(ROOT_ID, h) == fresh.get(ROOT_ID, h)


def test_compact_keeps_capacity_and_answers():
    idx = HashIndex()
    for i in range(24):
        idx.set(ROOT_ID, name_hash32(f"n{i}"), 10 + i)
    for i in range(0, 24, 2):
        idx.remove(ROOT_ID, name_hash32(f"n{i}"))
    cap, live = idx.cap, idx.live
    assert idx.used > live
    idx.compact()
    assert idx.cap == cap and idx.used == idx.live == live
    for i in range(24):
        want = -1 if i % 2 == 0 else 10 + i
        assert idx.get(ROOT_ID, name_hash32(f"n{i}")) == want


# ---------------------------------------------------------------------------
# window by window, against the rebuild path
# ---------------------------------------------------------------------------


def _rebuild_oracle(cview, nview, cache, caches):
    """The per-window rebuild the persistent snapshots replace."""
    return _fresh([cache]), _fresh(caches), 0, 0


def _assert_same_trace(a, b):
    assert (a.n, a.max_depth) == (b.n, b.max_depth)
    for f in ("type_ids", "depths", "parent_ids", "name_hashes", "hint_ids"):
        assert (getattr(a, f) == getattr(b, f)).all(), f
    assert a.resolved == b.resolved
    assert a.pks == b.pks
    assert a.target_ids == b.target_ids


@pytest.fixture
def small_cluster(monkeypatch):
    monkeypatch.setattr(columnar, "HINTCHAIN_MIN_BATCH", 16)
    monkeypatch.setattr(columnar, "PKVAL_MIN_BATCH", 16)
    store = ColumnarMetadataStore(n_datanodes=4)
    format_fs(store)
    cluster = NamenodeCluster(store, 3)
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=16, files_per_dir=4)
    materialize_namespace(cluster.namenodes[0], ns)
    return cluster, ns


@pytest.mark.parametrize("capacity", [1_000_000, 40])
@pytest.mark.parametrize("concurrent", [False, True])
def test_planned_windows_match_the_rebuild_path(small_cluster, monkeypatch,
                                                concurrent, capacity):
    cluster, ns = small_cluster
    real = batch_planner.lower_trace_fused
    launched = []

    def checked(wops, resolver, **kw):
        twin = HintResolver(resolver.cache, resolver.fallback)
        with monkeypatch.context() as m:
            m.setattr(columnar, "_snapshot_resolver", _rebuild_oracle)
            ct_ref, used_ref = real(wops, twin, **kw)
        before = (resolver.hits, resolver.fallback_hits, resolver.misses)
        ct, used = real(wops, resolver, **kw)
        after = (resolver.hits, resolver.fallback_hits, resolver.misses)
        assert used == used_ref
        assert tuple(x - y for x, y in zip(after, before)) \
            == (twin.hits, twin.fallback_hits, twin.misses)
        _assert_same_trace(ct, ct_ref)
        if used:
            # the kernel's (childs, srcs) over the window's chains: the
            # persistent views against fresh builds of the same contents
            cview, nview = columnar.snapshot_views(
                resolver.cache, tuple(resolver.fallback.caches))
            nam, dep = _encode([[c for c in w.path.split("/") if c]
                                for w in wops], depth=16)
            got = hintchain_ref(*cview.index.arrays(),
                                *nview.index.arrays(), nam, dep)
            want = hintchain_ref(*_fresh([resolver.cache]).arrays(),
                                 *_fresh(resolver.fallback.caches).arrays(),
                                 nam, dep)
            assert (got[0] == want[0]).all() and (got[1] == want[1]).all()
        launched.append(used)
        return ct, used

    monkeypatch.setattr(batch_planner, "lower_trace_fused", checked)
    cache = InodeHintCache(capacity=capacity)
    wops = make_spotify_trace(ns, 900, seed=11)
    reports = []
    for k, part in enumerate((wops[:300], wops[300:600], wops[600:])):
        if k == 2:
            cluster.kill(1)                   # membership changes
        pipe = PlannedRequestPipeline(cluster, concurrent=concurrent,
                                      window=100, adaptive=False,
                                      client_cache=cache)
        pipe.run(part)
        reports.append(pipe.plan_report)
    assert sum(launched) >= 6
    # persistent across pipelines: a later run rebuilds only the view
    # whose membership changed (or after a clear)
    assert reports[0].snapshot_rebuilds >= 2
    assert reports[1].snapshot_delta_keys > 0
    assert reports[2].snapshot_rebuilds >= 1


def test_one_namenode_view_for_every_client(small_cluster):
    """Planners over one set of namenode caches share one merged view, so
    each namenode cache carries one journal whatever the number of
    clients; a client's own view goes with its cache, journal and all."""
    cluster, ns = small_cluster
    wops = make_spotify_trace(ns, 200, seed=4)
    pipes = [PlannedRequestPipeline(cluster, window=100, adaptive=False)
             for _ in range(3)]
    for pipe in pipes:
        pipe.run(wops)
        assert pipe.plan_report.hintchain_launches
    alive = [nn.ops.cache for nn in cluster.alive_namenodes()]
    assert all(len(c._journals) == 1 for c in alive)
    views = {id(columnar.snapshot_views(p.client_cache, tuple(alive))[1])
             for p in pipes}
    assert len(views) == 1
    # only the first pipeline built the shared view
    assert [p.plan_report.snapshot_rebuilds for p in pipes] == [2, 1, 1]
    client = pipes[0].client_cache
    assert len(client._journals) == 1
    gone = weakref.ref(client)
    del pipes, client
    gc.collect()
    assert gone() is None
    assert all(len(c._journals) == 1 for c in alive)


@pytest.mark.parametrize("churn", ["new_keys", "invalidations"])
def test_undrained_journal_stays_bounded(churn):
    """A consumer that stops draining costs its cache at most
    ``capacity`` keys: the journal is then marked full and records
    nothing more, and the next refresh rebuilds to the cache's contents."""
    cache = InodeHintCache(capacity=64)
    view = HintSnapshot()
    for i in range(32):
        cache.put(ROOT_ID, f"k{i}", 100 + i)
    assert view.refresh((cache,)) == (1, 0)
    (journal,) = view._journals
    for i in range(5_000):
        if churn == "new_keys":
            cache.put(ROOT_ID + i % 7, f"n{i}", 1_000 + i)   # and evictions
        else:                               # other ids, then gone
            cache.put(ROOT_ID, f"k{i % 200}", 100 + i)
            cache.invalidate(ROOT_ID, f"k{(i + 7) % 200}")
        assert len(journal.keys) <= cache.capacity
    assert journal.full and not journal.keys
    assert view.refresh((cache,)) == (1, 0)
    fresh = _fresh([cache])
    probes = [(ROOT_ID + i % 7, f"n{i}") for i in range(5_000)] \
        + [(ROOT_ID, f"k{i}") for i in range(200)]
    for par, name in probes:
        h = name_hash32(name)
        assert view.index.get(par, h) == fresh.get(par, h)
    par, name, _ = cache.export_entries()[0]
    cache.invalidate(par, name)             # recording resumes
    assert view.refresh((cache,)) == (0, 1)
    assert view.index.get(par, name_hash32(name)) == -1


def test_refresh_while_namenode_threads_write():
    """Writers (one per namenode cache, as under ``concurrent=True``) put
    and invalidate while the planner's thread refreshes the merged view:
    once they stop, one more refresh matches a fresh snapshot, so no
    change recorded during a drain was lost."""
    caches = [InodeHintCache(capacity=400) for _ in range(6)]
    view = HintSnapshot()
    view.refresh(caches)
    stop = threading.Event()

    def write(k, cache):
        rng = random.Random(k)
        for i in range(3000):
            key = (ROOT_ID + rng.randrange(40), f"n{rng.randrange(300)}")
            if rng.random() < 0.8:
                cache.put(*key, 10 + rng.randrange(1000))
            else:
                cache.invalidate(*key)
        stop.set()

    writers = [threading.Thread(target=write, args=(k, c))
               for k, c in enumerate(caches)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for w in writers:
            w.start()
        while not stop.is_set():
            view.refresh(caches)
        for w in writers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(w.is_alive() for w in writers)
    view.refresh(caches)
    fresh = _fresh(caches)
    for par in range(ROOT_ID, ROOT_ID + 40):
        for n in range(300):
            h = name_hash32(f"n{n}")
            assert view.index.get(par, h) == fresh.get(par, h)


def test_cache_mutation_cannot_reach_a_finished_launch():
    """The launch copies the snapshot arrays; mutating the caches and
    refreshing the snapshots in place afterwards leaves its answer as it
    was, and the next launch sees the change."""
    from repro.kernels.hintchain.ops import hintchain_resolve
    tree = Tree()
    client, nn = InodeHintCache(), InodeHintCache()
    for key in tree.keys[::2]:
        client.put(*key, tree.id_of[key])
    for key in tree.keys[1::2]:
        nn.put(*key, tree.id_of[key])
    cview, fview = HintSnapshot(), HintSnapshot()
    cview.refresh((client,))
    fview.refresh((nn,))
    nam, dep = _encode(tree.chains(random.Random(3), 64))
    before = [a.copy() for a in cview.index.arrays() + fview.index.arrays()]
    childs, srcs = hintchain_resolve(cview.index.arrays(),
                                     fview.index.arrays(), nam, dep)
    kept = childs.copy(), srcs.copy()
    for key in tree.keys:
        client.put(*key, tree.id_of[key] + 50_000)
        nn.invalidate(*key)
    cview.refresh((client,))
    fview.refresh((nn,))
    assert (childs == kept[0]).all() and (srcs == kept[1]).all()
    want = hintchain_ref(*before, nam, dep)
    assert (childs == want[0]).all() and (srcs == want[1]).all()
    again = hintchain_resolve(cview.index.arrays(), fview.index.arrays(),
                              nam, dep)
    assert not (again[0] == childs).all()


def test_lower_trace_fused_falls_back_for_unjournaled_caches():
    """A fallback cache that keeps no journal cannot be snapshotted: the
    window takes the exact Python walk."""
    class Plain:
        def peek(self, parent_id, name):
            return None

    r = HintResolver(InodeHintCache(), MultiCacheResolver([Plain()]))
    ops = make_spotify_trace(
        SyntheticNamespace(NamespaceSpec(), n_dirs=4, files_per_dir=2),
        40, seed=1)
    _, used = lower_trace_fused(ops, r, min_batch=2)
    assert not used and r.snapshot_rebuilds == 0
