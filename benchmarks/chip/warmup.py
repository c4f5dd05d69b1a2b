"""Compiles, during set-up, every kernel shape bucket a window can use.

The kernels pad their batch dimension to a power of two, so the shapes a
window can produce are known ahead: pkval over the inode index with
128..8,192 probes, hintchain over hint-cache snapshots with 16..1,024
ops, phash_chain with 512 or 1,024 ops, treeagg over the hot columns
with waves of 8..64 members. The hint-cache snapshots grow as clients
touch new paths, so hintchain is also compiled for snapshot capacities
two and four times today's. Each bucket is compiled by one call of the
kernel's public wrapper on placeholder inputs of that shape.

A wrapper that fails here (a changed signature, say) is reported and
skipped: the run goes on, and its compile count inside the window shows
what was missed.
"""
from __future__ import annotations

from typing import Callable, List

import numpy as np

#: planner windows are capped at this many ops (chip_smoke.py's window)
WINDOW_CAP = 1024
MAX_DEPTH = 16          # lower_trace_fused's component bound
PATH_DEPTH = 8          # deepest traffic path: a tree root, 6 dirs, a file


def pow2s(lo: int, hi: int) -> List[int]:
    out, p = [], 8
    while p < lo:
        p *= 2
    while p <= hi:
        out.append(p)
        p *= 2
    return out


#: (client, fallback) snapshot capacities compiled, as multiples of
#: today's: both hint-cache snapshots grow as clients touch new paths
CAP_STEPS = ((1, 1), (2, 1), (1, 2), (2, 2), (4, 2), (2, 4), (4, 4))


def snapshot_caps(cluster, client) -> tuple:
    """Capacities of the two hint-cache snapshots the planner's
    ``lower_trace_fused`` builds now: the client cache, and the alive
    namenodes' caches merged first-cache-wins (as ``_snapshot_resolver``
    in src/repro/core/columnar.py merges them)."""
    from repro.core.columnar import HashIndex
    merged: dict = {}
    for nn in cluster.alive_namenodes():
        if nn.ops.cache is not None:
            for par, name, iid in nn.ops.cache.export_entries():
                merged.setdefault((par, name), iid)
    client_idx = HashIndex.from_entries(client.hint_cache.export_entries())
    fallback_idx = HashIndex.from_entries(
        (par, name, iid) for (par, name), iid in merged.items())
    return client_idx.cap, fallback_idx.cap


def _empty_index(cap: int):
    return (np.full(cap, -1, np.int32), np.zeros(cap, np.uint32),
            np.full(cap, -1, np.int32))


def warm_kernels(store, cluster, client, cap: int,
                 log: Callable[[str], None]) -> None:
    from repro.core.columnar import (HINTCHAIN_MIN_BATCH, PKVAL_MIN_BATCH)
    from repro.core.namenode import PHASH_MIN_BATCH
    table = store.table("inode")
    steps = []

    def pkval():
        from repro.kernels.pkval.ops import pkval_lookup
        tp, tn, tv = table.hindex.arrays()
        for n in pow2s(PKVAL_MIN_BATCH, cap * PATH_DEPTH):
            pkval_lookup(tp, tn, tv, np.full(n, -1, np.int64),
                         np.zeros(n, np.int64))
    steps.append(("pkval", pkval))

    def hintchain():
        from repro.kernels.hintchain.ops import hintchain_resolve
        c_cap, f_cap = snapshot_caps(cluster, client)
        log(f"kernel warm-up: hint-cache snapshots of {c_cap} and {f_cap} "
            f"slots now")
        for c, f in CAP_STEPS:
            cidx = _empty_index(c_cap * c)
            fidx = _empty_index(f_cap * f)
            for n in pow2s(HINTCHAIN_MIN_BATCH // PATH_DEPTH, cap):
                hintchain_resolve(cidx, fidx,
                                  np.zeros((n, MAX_DEPTH), np.int64),
                                  np.zeros(n, np.int32))
    steps.append(("hintchain", hintchain))

    def phash_chain():
        from repro.kernels.phash.ops import phash_chains
        for n in pow2s(PHASH_MIN_BATCH, cap):
            z = np.zeros((n, MAX_DEPTH), np.int64)
            phash_chains(z, z, np.zeros(n, np.int64), np.zeros(n, np.int32),
                         store.n_partitions)
    steps.append(("phash_chain", phash_chain))

    def treeagg():
        from repro.kernels.treeagg.ops import treeagg_expand
        slots = len(table.hot_column("parent_id"))
        z = np.zeros(slots, np.int64)
        for w in pow2s(8, 64):
            treeagg_expand(np.arange(w, dtype=np.int64), z - 1, z, z)
    steps.append(("treeagg", treeagg))

    for name, step in steps:
        try:
            step()
        except Exception as e:      # reported; the window shows the cost
            log(f"kernel warm-up {name} skipped: {type(e).__name__}: {e}")
