"""Kernel vs numpy-oracle regression for the columnar engine's two fused
lookups — grouped PK validation (``pkval``) and vectorized hint-chain
resolution (``hintchain``), both jitted XLA programs that run on whatever
backend JAX has (the CPU under ``JAX_PLATFORMS=cpu``) — mirroring the
phash suite's pattern, plus ``_KernelProbe`` fallback-and-recovery
coverage for the per-family availability gates, whose host fallback is
taken only where ``repro.kernels.mode`` says kernels are interpreted."""
import jax
import numpy as np
import pytest

import repro.core.columnar as columnar
from repro.core.columnar import AMBIG, EMPTY, HashIndex, MAX_PROBE
from repro.core.namenode import _KernelProbe, _with_phash_kernel
from repro.core.workload import name_hash32


def _filled_index(n=300, seed=0, offset=0):
    rng = np.random.default_rng(seed)
    idx = HashIndex()
    keys = []
    for i in range(n):
        par = int(rng.integers(1, 50_000)) + offset
        nam = name_hash32(f"e{seed}_{i}")
        idx.set(par, nam, i + 2)
        keys.append((par, nam, i + 2))
    return idx, keys


# ---------------------------------------------------------------------------
# pkval
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_probes", [8, 129, 1000])
def test_pkval_kernel_matches_ref(n_probes):
    from repro.kernels.pkval.ops import pkval_lookup
    from repro.kernels.pkval.ref import pkval_ref
    idx, keys = _filled_index(400, seed=1)
    rng = np.random.default_rng(2)
    probes = []
    for i in range(n_probes):
        if rng.random() < 0.6:
            par, nam, _ = keys[int(rng.integers(len(keys)))]
        else:
            par, nam = int(rng.integers(60_000, 90_000)), \
                name_hash32(f"miss{i}")
        probes.append((par, nam))
    par = np.array([p for p, _ in probes], np.int64)
    nam = np.array([h for _, h in probes], np.int64)
    tp, tn, tv = idx.arrays()
    out = pkval_lookup(tp, tn, tv, par, nam)
    ref = pkval_ref(tp, tn, tv, par.astype(np.int32),
                    nam.astype(np.uint32))
    assert out.shape == (n_probes,)
    assert (out == ref).all()
    # ... and both agree with the host index's own exact probes
    for i, (p, h) in enumerate(probes):
        assert int(out[i]) == idx.get(p, h)


def test_pkval_probe_bound_respected_across_growth():
    """The host index grows rather than placing an entry beyond
    MAX_PROBE, so the kernel's bounded probe NEVER misses a live key."""
    from repro.kernels.pkval.ref import pkval_ref
    idx, keys = _filled_index(2000, seed=4)
    tp, tn, tv = idx.arrays()
    par = np.array([k[0] for k in keys], np.int32)
    nam = np.array([k[1] for k in keys], np.uint32)
    out = pkval_ref(tp, tn, tv, par, nam)
    want = np.array([k[2] for k in keys], np.int32)
    assert (out == want).all()


def test_pkval_empty_and_padding():
    from repro.kernels.pkval.ops import pkval_lookup
    idx, _ = _filled_index(10, seed=5)
    tp, tn, tv = idx.arrays()
    assert pkval_lookup(tp, tn, tv, np.zeros(0, np.int64),
                        np.zeros(0, np.int64)).shape == (0,)
    # non-power-of-two probe counts pad with always-miss parents
    out = pkval_lookup(tp, tn, tv, np.array([123456789], np.int64),
                       np.array([7], np.int64))
    assert out.shape == (1,) and int(out[0]) == EMPTY


# ---------------------------------------------------------------------------
# hintchain
# ---------------------------------------------------------------------------

def _chain_fixture(seed=0, n=64, d=5):
    """Build client/fallback indexes over a synthetic tree plus [n, d]
    chain matrices with known expected resolutions."""
    rng = np.random.default_rng(seed)
    client = HashIndex()
    fall = HashIndex()
    # a two-level namespace: /dirX/fileY with ids laid out predictably
    dirs = {}
    for x in range(20):
        did = 10 + x
        dirs[x] = did
        (client if x % 2 == 0 else fall).set(1, name_hash32(f"d{x}"), did)
    for x in range(20):
        for y in range(6):
            fid = 1000 + x * 10 + y
            (client if y % 3 == 0 else fall).set(
                dirs[x], name_hash32(f"f{y}"), fid)
    nam = np.zeros((n, d), np.uint32)
    dep = np.zeros(n, np.int32)
    for i in range(n):
        x = int(rng.integers(0, 24))          # some dirs don't exist
        y = int(rng.integers(0, 8))           # some files don't exist
        nam[i, 0] = name_hash32(f"d{x}")
        nam[i, 1] = name_hash32(f"f{y}")
        dep[i] = 2
    return client, fall, nam, dep


@pytest.mark.parametrize("seed", [0, 3])
def test_hintchain_kernel_matches_ref(seed):
    from repro.kernels.hintchain.ops import hintchain_resolve
    from repro.kernels.hintchain.ref import hintchain_ref
    client, fall, nam, dep = _chain_fixture(seed=seed, n=70, d=5)
    cp, cn, cv = client.arrays()
    fp, fn, fv = fall.arrays()
    childs, srcs = hintchain_resolve((cp, cn, cv), (fp, fn, fv), nam, dep)
    rch, rsr = hintchain_ref(cp, cn, cv, fp, fn, fv, nam, dep)
    assert childs.shape == nam.shape
    assert (childs == rch).all()
    assert (srcs == rsr).all()


def test_hintchain_resolution_semantics():
    """Spot-check the (child, src) encoding against hand walks: client
    precedence, fallback hits, chain stop at first miss, dead ops."""
    from repro.kernels.hintchain.ref import hintchain_ref
    client, fall, nam, dep = _chain_fixture(seed=1, n=40, d=5)
    cp, cn, cv = client.arrays()
    fp, fn, fv = fall.arrays()
    childs, srcs = hintchain_ref(cp, cn, cv, fp, fn, fv, nam, dep)
    for i in range(nam.shape[0]):
        parent = 1
        alive = True
        for d in range(int(dep[i])):
            cval = client.get(parent, int(nam[i, d]))
            fval = fall.get(parent, int(nam[i, d]))
            want = cval if cval != EMPTY else fval
            if not alive:
                assert int(childs[i, d]) == -2
                continue
            if want > 0:
                assert int(childs[i, d]) == want
                assert int(srcs[i, d]) == (0 if cval > 0 else 1)
                parent = want
            else:
                assert int(childs[i, d]) == EMPTY
                assert int(srcs[i, d]) == -1
                alive = False
        for d in range(int(dep[i]), nam.shape[1]):
            assert int(childs[i, d]) == -2


def test_hintchain_ambig_passthrough(monkeypatch):
    """A poisoned client bucket must surface AMBIG, not a fake hit, and
    must NOT fall through to the fallback table."""
    from repro.kernels.hintchain.ref import hintchain_ref
    client = HashIndex()
    client.set(1, 42, AMBIG)
    fall = HashIndex()
    fall.set(1, 42, 777)
    nam = np.array([[42]], np.uint32)
    dep = np.array([1], np.int32)
    childs, srcs = hintchain_ref(*client.arrays(), *fall.arrays(),
                                 nam, dep)
    assert int(childs[0, 0]) == AMBIG
    assert int(srcs[0, 0]) == -1


def test_hintchain_empty_window():
    from repro.kernels.hintchain.ops import hintchain_resolve
    idx = HashIndex()
    childs, srcs = hintchain_resolve(idx.arrays(), idx.arrays(),
                                     np.zeros((0, 4), np.uint32),
                                     np.zeros(0, np.int32))
    assert childs.shape == (0, 4) and srcs.shape == (0, 4)


# ---------------------------------------------------------------------------
# treeagg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_slots,n_wave", [(8, 1), (300, 7), (5000, 130)])
def test_treeagg_kernel_matches_ref(n_slots, n_wave):
    """Wave expansion + segment sums agree bit for bit with the oracle:
    cleared slots (parent -1), non-members, and int32 size sums that
    wrap past 2^31 exactly like the oracle's."""
    from repro.kernels.treeagg.ops import treeagg_expand
    from repro.kernels.treeagg.ref import treeagg_ref
    rng = np.random.default_rng(n_slots)
    wave = np.unique(rng.choice(np.arange(2, 400), n_wave, replace=False))
    par = rng.integers(-1, 400, n_slots)
    isdir = rng.integers(0, 2, n_slots)
    size = rng.integers(0, 2**31 - 1, n_slots)
    got = treeagg_expand(wave, par, isdir, size)
    want = treeagg_ref(wave, par, isdir, size)
    for g, w in zip(got, want):
        assert g.dtype == np.int32 and (g == w).all()
    seg = got[0]
    assert ((seg >= 0) == np.isin(par, wave)).all()


# ---------------------------------------------------------------------------
# _KernelProbe fallback & recovery (per-family gates)
# ---------------------------------------------------------------------------

def test_kernel_probe_fallback_and_bounded_recovery():
    probe = _KernelProbe(reprobe_every=4)
    calls = {"kern": 0, "fall": 0}

    def bad_kernel():
        calls["kern"] += 1
        raise RuntimeError("accelerator hiccup")

    def fallback():
        calls["fall"] += 1
        return "fallback"

    out, used = _with_phash_kernel(bad_kernel, fallback, n_keys=100,
                                   min_batch=2, probe=probe)
    assert out == "fallback" and not used and probe.failures == 1
    # while latched, eligible calls use the fallback without probing...
    for _ in range(3):
        out, used = _with_phash_kernel(bad_kernel, fallback, n_keys=100,
                                       min_batch=2, probe=probe)
        assert not used
    assert calls["kern"] == 1
    # ...until the bounded re-probe window elapses and the (recovered)
    # kernel is tried again
    def good_kernel():
        calls["kern"] += 1
        return "kernel"

    out, used = _with_phash_kernel(good_kernel, fallback, n_keys=100,
                                   min_batch=2, probe=probe)
    assert out == "kernel" and used and probe.failures == 0


def test_kernel_probe_counts_launches_and_demotions():
    probe = _KernelProbe()
    _with_phash_kernel(lambda: "k", lambda: "f", n_keys=1, min_batch=2,
                       probe=probe)
    assert (probe.launches, probe.demotions) == (0, 0)   # below the gate
    _with_phash_kernel(lambda: "k", lambda: "f", n_keys=4, min_batch=2,
                       probe=probe)
    assert (probe.launches, probe.demotions) == (1, 0)

    def bad():
        raise RuntimeError("kernel bug")

    out, used = _with_phash_kernel(bad, lambda: "f", n_keys=4, min_batch=2,
                                   probe=probe)
    assert (out, used) == ("f", False)
    assert (probe.launches, probe.demotions) == (1, 1)


def test_compiled_kernel_failure_propagates(monkeypatch):
    """Where kernels run compiled (the TPU), a failing kernel raises out
    of the gate instead of answering from the host oracle — and is still
    counted as a demotion."""
    from repro.kernels import mode
    monkeypatch.setattr(mode, "interpret", lambda: False)
    probe = _KernelProbe()

    def bad():
        raise RuntimeError("kernel bug")

    with pytest.raises(RuntimeError, match="kernel bug"):
        _with_phash_kernel(bad, lambda: "f", n_keys=4, min_batch=2,
                           probe=probe)
    assert (probe.launches, probe.demotions) == (0, 1)


def test_kernel_mode_follows_backend(monkeypatch):
    from repro.kernels import mode
    assert mode.interpret() is (jax.default_backend() == "cpu")
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert mode.interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        mode.interpret()


def test_kernel_probe_families_are_independent():
    columnar._pkval_probe.failed()
    try:
        assert not columnar._pkval_probe.usable()
        assert columnar._hintchain_probe.usable()
    finally:
        columnar._pkval_probe.succeeded()


def test_lower_trace_fused_survives_kernel_failure(monkeypatch):
    """If the hintchain kernel raises, the probe latches the numpy oracle
    and the fused lowering still returns the exact Python-walk result."""
    from repro.core import (NamenodeCluster, format_fs,
                            materialize_namespace)
    from repro.core.batch_planner import HintResolver, MultiCacheResolver
    from repro.core.columnar import ColumnarMetadataStore, lower_trace_fused
    from repro.core.hint_cache import InodeHintCache
    from repro.core.workload import (NamespaceSpec, SyntheticNamespace,
                                     lower_trace, make_spotify_trace)
    import repro.kernels.hintchain.ops as hc_ops

    store = ColumnarMetadataStore(n_datanodes=4)
    format_fs(store)
    cluster = NamenodeCluster(store, 1)
    ns = SyntheticNamespace(NamespaceSpec(), n_dirs=8, files_per_dir=4)
    materialize_namespace(cluster.namenodes[0], ns)
    wops = make_spotify_trace(ns, 60, seed=2)

    def resolver():
        return HintResolver(InodeHintCache(),
                            MultiCacheResolver.of_cluster(cluster))

    monkeypatch.setattr(columnar, "HINTCHAIN_MIN_BATCH", 2)

    def boom(*a, **kw):
        raise RuntimeError("no accelerator")

    monkeypatch.setattr(hc_ops, "hintchain_resolve", boom)
    r1 = resolver()
    ct_fused, used = lower_trace_fused(wops, r1)
    assert not used                       # oracle fallback, not the kernel
    r2 = resolver()
    ct_ref = lower_trace(wops, r2)
    assert ct_fused.resolved == ct_ref.resolved
    assert ct_fused.pks == ct_ref.pks
    assert ct_fused.target_ids == ct_ref.target_ids
    assert (ct_fused.depths == ct_ref.depths).all()
    assert (ct_fused.hint_ids == ct_ref.hint_ids).all()
    assert (r1.hits, r1.fallback_hits, r1.misses) \
        == (r2.hits, r2.fallback_hits, r2.misses)
    columnar._hintchain_probe.succeeded()   # don't leak latched state
