"""Smoke run of the planned metadata path, compiled, on one TPU chip.

    python chip_smoke.py

One process loads a 4-namenode ``ColumnarMetadataStore`` cluster with a
Spotify-shaped namespace (through the normal ops) and one directory of
10^6 files, replays a Spotify trace through
``DFSClient.run_trace(planned=True)`` at a window of 1,024 ops (which
opens the ``phash_chain``, ``hintchain`` and ``pkval`` gates), then runs
``du`` on the big directory and a recursive delete of a 10^4-entry side
directory (``treeagg``). The same ops replayed on dict ``MetadataStore``
oracles must give the same namespace (sequential replay) and the same
``dump_state`` and per-op outcomes (planned replay).

Every kernel family must show at least one launch and no demotion to the
host oracle. The phase times it prints are set-up and smoke times, not
metrics. Only when every check passed is the last line
``{"ok": true, "device": {...}}``. Exits non-zero without a TPU.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_NAMENODES = 4
BIG_DIR = "/big"
BIG_CHILDREN = 1_000_000
SIDE_DIR = "/side"
SIDE_CHILDREN = 10_000
N_OPS = 4096
WINDOW = 1024
SEED = 17
FAMILIES = ("phash_chain", "hintchain", "pkval", "treeagg")


def _probes():
    from repro.core import batch_planner, columnar
    return {"phash_chain": batch_planner._phash_chain_probe,
            "hintchain": columnar._hintchain_probe,
            "pkval": columnar._pkval_probe,
            "treeagg": columnar._treeagg_probe}


def _build(store_cls, n_namenodes, ns, n_big, n_side):
    from repro.core import (NamenodeCluster, format_fs, materialize_big_dir,
                            materialize_namespace)
    store = store_cls(n_datanodes=4)
    format_fs(store)
    cluster = NamenodeCluster(store, n_namenodes)
    nn = cluster.namenodes[0]
    materialize_namespace(nn, ns)
    materialize_big_dir(nn, BIG_DIR, n_big)
    materialize_big_dir(nn, SIDE_DIR, n_side, file_prefix="s")
    return store, cluster


def _tail_ops(client):
    """``du`` of the big directory, then a recursive delete of the side
    directory: the subtree ops that drive ``treeagg``."""
    du = client.call("du", BIG_DIR).value
    deleted = client.delete(SIDE_DIR, recursive=True)
    return du, deleted.deleted


def run(*, n_big: int = BIG_CHILDREN, n_side: int = SIDE_CHILDREN,
        n_ops: int = N_OPS, log=print) -> bool:
    """Load, replay and check; ``log`` gets one line per fact. Returns
    whether every check passed. The sizes are parameters so the checks
    can also run on a tiny namespace on the CPU."""
    from repro.core import (DFSClient, MetadataStore, RequestPipeline,
                            namespace_snapshot)
    from repro.core.columnar import ColumnarMetadataStore
    from repro.core.workload import (NamespaceSpec, SyntheticNamespace,
                                     make_spotify_trace)

    ns = SyntheticNamespace(NamespaceSpec())
    wops = make_spotify_trace(ns, n_ops, seed=SEED)
    checks = {}
    probes = _probes()
    base = {fam: (p.launches, p.demotions) for fam, p in probes.items()}

    def counts(fam):
        p = probes[fam]
        return p.launches - base[fam][0], p.demotions - base[fam][1]

    t0 = time.perf_counter()
    store, cluster = _build(ColumnarMetadataStore, N_NAMENODES, ns, n_big,
                            n_side)
    live = store.table("inode").n_rows
    log(f"set-up time, columnar load: {time.perf_counter() - t0:.3f} s")
    log(f"live inodes: {live}")
    log(f"inode index slots: {store.table('inode').hindex.cap}")
    log(f"ops: {len(wops)} trace + du + delete_subtree")

    t0 = time.perf_counter()
    client = DFSClient(cluster)
    stats = client.run_trace(list(wops), planned=True, window=WINDOW,
                             adaptive=False)
    du, deleted = _tail_ops(client)
    log(f"smoke time, planned replay + du + delete: "
        f"{time.perf_counter() - t0:.3f} s")
    log(f"trace ops failed: {stats.failed}")
    log(f"du {BIG_DIR}: {du}")
    log(f"delete {SIDE_DIR}: {deleted} inodes")
    checks["du counts the big directory"] = du["inodes"] == n_big + 1
    checks["delete removes the side directory"] = deleted == n_side + 1

    for fam in FAMILIES:
        launches, demotions = counts(fam)
        log(f"kernel {fam}: launches={launches} demotions={demotions}")
        checks[f"{fam} launched"] = launches > 0
        checks[f"{fam} not demoted"] = demotions == 0
    nn_demotions = sum(nn.treeagg_demotions for nn in cluster.namenodes)
    log(f"namenode treeagg demotions: {nn_demotions}")
    checks["no namenode treeagg demotion"] = nn_demotions == 0
    dump = store.dump_state()
    outcomes = [(o.ok, o.error, o.result.value if o.ok else None)
                for o in stats.outcomes]
    del store, cluster, client

    t0 = time.perf_counter()
    dstore, dcluster = _build(MetadataStore, N_NAMENODES, ns, n_big, n_side)
    dclient = DFSClient(dcluster)
    dstats = dclient.run_trace(list(wops), planned=True, window=WINDOW,
                               adaptive=False)
    dtail = _tail_ops(dclient)
    log(f"set-up time, planned dict oracle: {time.perf_counter() - t0:.3f} s")
    checks["dump_state equals planned dict replay"] = \
        dump == dstore.dump_state()
    checks["per-op outcomes equal planned dict replay"] = outcomes == [
        (o.ok, o.error, o.result.value if o.ok else None)
        for o in dstats.outcomes]
    checks["du and delete equal planned dict replay"] = \
        (du, deleted) == dtail
    snap = namespace_snapshot(dstore)
    del dstore, dcluster, dclient, dump

    t0 = time.perf_counter()
    sstore, scluster = _build(MetadataStore, 1, ns, n_big, n_side)
    sstats = RequestPipeline(scluster, batch_size=1).run(list(wops))
    stail = _tail_ops(DFSClient(scluster))
    log(f"set-up time, sequential dict oracle: "
        f"{time.perf_counter() - t0:.3f} s")
    checks["namespace equals sequential dict replay"] = \
        snap == namespace_snapshot(sstore)
    checks["failed count equals sequential dict replay"] = \
        stats.failed == sstats.failed
    checks["du and delete equal sequential dict replay"] = \
        (du, deleted) == stail
    checks["no demotion in the oracle replays either"] = all(
        counts(fam)[1] == 0 for fam in FAMILIES)

    for name, ok in checks.items():
        log(f"check {'PASS' if ok else 'FAIL'}: {name}")
    return all(checks.values())


def main() -> int:
    import jax
    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {dev.platform!r});"
              f" the smoke runs only on the chip", file=sys.stderr)
        return 1
    try:
        from repro.kernels import mode
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: "
              f"{e}", file=sys.stderr)
        return 1
    print(f"compile cache: {mode.use_compile_cache()}", flush=True)
    if not run(log=lambda line: print(line, flush=True)):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
