"""Phase-3 chunks committed as one batch (one scan call per file table,
batched locks and tombstones, bulk store deletes) against the per-row
reference: today's loop of one ``_file_scan`` and one ``delete`` per
inode through the public ``Transaction`` API, flushed row by row. Both
leave the same store, per-chunk costs, row ops and lock counters, on
both stores; a failure inside a chunk leaves the store as it was and no
lock held."""
import types

import numpy as np
import pytest

from repro.core import MetadataStore, NamenodeCluster, WorkloadOp, format_fs
from repro.core.columnar import ColumnarMetadataStore, ColumnarTable
from repro.core.store import (EXCLUSIVE, LockTimeout, NodeGroupDown,
                              PagedBucket, Table)
from repro.core.subtree import FILE_TABLES
from repro.core.tables import ROOT_ID
from repro.core.transactions import Transaction

STORES = {"dict": MetadataStore, "columnar": ColumnarMetadataStore}


def _build(kind):
    """One namenode over a store holding /t: 24 files (:func:`_file`) in
    /t, /t/a and /t/a/b, an empty /t/c, and 150 more empty files in /t,
    so that chunks of 100 rows have many rows for the bulk store
    delete."""
    store = STORES[kind](n_datanodes=4)
    format_fs(store)
    nn = NamenodeCluster(store, 1).namenodes[0]
    ops = nn.ops
    ops.mkdirs("/t/a/b")
    ops.mkdirs("/t/c")
    for i in range(24):
        path = _file(i)
        ops.create(path)
        kind_of = i % 4
        if kind_of == 0:
            continue
        bid = ops.add_block(path).value
        if kind_of == 1:
            ops.complete_block(path, bid, size=10)
        elif kind_of == 2:
            iid = _inode_id(store, path)
            for dn in (0, 1, 2):
                store.table("ruc").put({"block_id": bid, "datanode_id": dn,
                                        "inode_id": iid})
        else:
            ops.complete_block(path, bid, size=10)
            ops.truncate(path, 0)          # replicas become inv rows
    for i in range(150):
        ops.create(f"/t/e{i:03d}")
    return store, nn


def _file(i):
    """The i-th test file, in /t, /t/a or /t/a/b. By i % 4 it has no row
    beside its inode, a completed block (block and replica rows), a block
    under construction (block and ruc rows), or invalidated replicas (inv
    rows)."""
    return f"{('/t', '/t/a', '/t/a/b')[i % 3]}/g{i:02d}"


def _inode_id(store, path):
    cur = ROOT_ID
    for name in path.strip("/").split("/"):
        cur = store.table("inode").get((cur, name))["id"]
    return cur


def _per_row_commit_chunk(self, chunk):
    """The reference: one ``_file_scan`` and one ``delete`` per inode."""
    with Transaction(self.store, partition_hint=("inode", chunk[0][1]),
                     distribution_aware=self.ops.dat) as txn:
        for iid, pid, name, is_dir in chunk:
            if not is_dir:
                related = self.ops._file_scan(txn, FILE_TABLES, iid,
                                              EXCLUSIVE)
                for tname, rws in related.items():
                    pk = self.store.table(tname).schema.pk
                    for r in rws:
                        txn.delete(tname, tuple(r[c] for c in pk))
            txn.delete("inode", (pid, name))
            if self.ops.cache:
                self.ops.cache.invalidate(pid, name)
        return txn.commit()


def _delete_row_by_row(self, pks):
    return sum(bool(self.delete(pk)) for pk in pks)


def _per_row(nn, monkeypatch):
    """Make ``nn``'s subtree ops run the reference, and every commit
    flush its tombstones with one store ``delete`` per row."""
    nn.subtree._commit_chunk = types.MethodType(_per_row_commit_chunk,
                                                nn.subtree)
    for cls in (Table, ColumnarTable):
        monkeypatch.setattr(cls, "delete_many", _delete_row_by_row)


def _bucket(b):
    return list(b) if isinstance(b, PagedBucket) else sorted(b, key=repr)


def _layout(store):
    """What the store holds beyond ``dump_state``: each index's buckets in
    their paging order and, on the columnar tables, slot placement, the
    free list, the hot arrays and the inode hash index."""
    out = {}
    for name, t in store.tables.items():
        d = {"idx": {c: {v: _bucket(b) for v, b in ix.items()}
                     for c, ix in t.idx.items()},
             "n_rows": t.n_rows, "pk_loc": t._pk_loc}
        if isinstance(t, ColumnarTable):
            top = t._top
            d.update(free=list(t._free), top=top, slots=list(t._slots.items()),
                     part_slots=t.part_slots[:top].tolist(),
                     hot={c: a[:top].tolist() for c, a in t._hot.items()},
                     cols={c: col[:top] for c, col in t._cols.items()})
            if t.hindex is not None:
                h = t.hindex
                d.update(namehash=t._namehash[:top].tolist(), hkey=t._hkey,
                         hindex=(h.cap, h.used, h.live,
                                 np.concatenate([h.par, h.val]).tolist(),
                                 h.nam.tolist()))
        out[name] = d
    return out


def _observe(store, nn, res):
    st = nn.subtree.last_stats
    return {"value": res.value, "cost": res.cost.as_dict(),
            "chunk_costs": st["chunk_costs"], "chunks": st["chunks"],
            "deleted": st["deleted"],
            "state": store.dump_state(),
            "layout": _layout(store), "row_ops": store.total_row_ops,
            "acquires": store.locks.acquire_count,
            "waits": store.locks.wait_count}


@pytest.mark.parametrize("kind", sorted(STORES))
@pytest.mark.parametrize("incremental", [True, False])
@pytest.mark.parametrize("batch", [7, 100])
def test_batched_delete_matches_the_per_row_reference(kind, incremental,
                                                      batch, monkeypatch):
    """The whole delete, streamed (files in chunks of their own) and
    legacy (post-order chunks that mix directories and files), in small
    chunks and large."""
    out = []
    for reference in (False, True):
        store, nn = _build(kind)
        nn.subtree.batch_size = batch
        nn.subtree.incremental = incremental
        with monkeypatch.context() as m:
            if reference:
                _per_row(nn, m)
            res = nn.invoke(WorkloadOp("delete_subtree", "/t", on_dir=True))
        out.append(_observe(store, nn, res))
    batched, ref = out
    assert batched["value"] == {"deleted": 1 + 3 + 24 + 150,
                                "crashed": False}
    for key in batched:
        assert batched[key] == ref[key], key


def _mixed_chunk(store):
    """Every inode under /t, directories among files, as one chunk."""
    t = store.table("inode")
    chunk, stack = [], [_inode_id(store, "/t")]
    while stack:
        for k in t.scan_index("parent_id", stack.pop()):
            chunk.append((k["id"], k["parent_id"], k["name"], k["is_dir"]))
            if k["is_dir"]:
                stack.append(k["id"])
    return chunk


@pytest.mark.parametrize("kind", sorted(STORES))
def test_one_mixed_chunk_matches_the_per_row_reference(kind, monkeypatch):
    out = []
    for reference in (False, True):
        store, nn = _build(kind)
        chunk = _mixed_chunk(store)
        assert {n[3] for n in chunk} == {True, False}
        assert all(store.table(t).n_rows for t in FILE_TABLES)
        with monkeypatch.context() as m:
            if reference:
                _per_row(nn, m)
            cost = nn.subtree._commit_chunk(chunk)
        out.append((cost.as_dict(), store.dump_state(), _layout(store),
                    store.total_row_ops, store.locks.acquire_count,
                    store.locks.wait_count))
    assert out[0] == out[1]
    assert all(not out[0][1][t] for t in FILE_TABLES)
    assert out[0][1]["inode"] == store.dump_state()["inode"]
    assert len(out[0][1]["inode"]) == 2          # / and /t


def _fail_group_of_some_file(store, chunk):
    """Fail the node group that holds some file's rows but not the
    chunk's anchor, so the failure comes inside the chunk."""
    anchor = store.table("inode").partition_of(chunk[0][1])
    keep = store.group_of_partition(anchor).gid
    for iid, _, _, is_dir in chunk:
        g = store.group_of_partition(store.table("block").partition_of(iid))
        if not is_dir and g.gid != keep:
            for dn in list(g.datanodes):
                store.fail_datanode(dn)
            return g.datanodes
    raise AssertionError("every file shares the anchor's node group")


@pytest.mark.parametrize("kind", sorted(STORES))
@pytest.mark.parametrize("reference", [False, True])
def test_node_group_down_mid_chunk_changes_nothing(kind, reference,
                                                   monkeypatch):
    store, nn = _build(kind)
    chunk = _mixed_chunk(store)
    before = (store.dump_state(), _layout(store))
    failed = _fail_group_of_some_file(store, chunk)
    if reference:
        _per_row(nn, monkeypatch)
    with pytest.raises(NodeGroupDown):
        nn.subtree._commit_chunk(chunk)
    for dn in failed:
        store.recover_datanode(dn)
    assert store.locks.held_count(store._txn_seq) == 0
    assert all(not d for d in store.locks._locks) and not store.locks._held
    assert (store.dump_state(), _layout(store)) == before


@pytest.mark.parametrize("kind", sorted(STORES))
def test_lock_timeout_mid_chunk_changes_nothing(kind):
    """Another transaction holds the chunk's last inode row: the chunk
    waits, times out and aborts, with every lock it took released."""
    store, nn = _build(kind)
    chunk = _mixed_chunk(store)
    before = (store.dump_state(), _layout(store))
    store.locks.timeout = 0.03
    other = store.next_txn_id()
    store.locks.acquire(other, "inode", (chunk[-1][1], chunk[-1][2]),
                        EXCLUSIVE)
    waits = store.locks.wait_count
    with pytest.raises(LockTimeout):
        nn.subtree._commit_chunk(chunk)
    assert store.locks.wait_count == waits + 1
    assert store.locks.held_count(store._txn_seq) == 0
    assert set(store.locks._held) == {other}
    store.locks.release_all(other)
    assert all(not d for d in store.locks._locks)
    assert (store.dump_state(), _layout(store)) == before


@pytest.mark.parametrize("kind", sorted(STORES))
@pytest.mark.parametrize("coordinated", [True, False])
def test_ppis_many_matches_ppis_with_the_overlay(kind, coordinated):
    """A transaction that wrote and deleted rows of the scanned table
    sees them in ``ppis_many`` as in one ``ppis`` per value: the same
    rows, cost, row ops and locks, the transaction's own writes
    overlaid."""
    out = []
    for batched in (True, False):
        store, nn = _build(kind)
        files = [_inode_id(store, _file(i)) for i in (0, 1, 5, 4)]
        hint = ("inode", ROOT_ID) if coordinated else None
        with Transaction(store, partition_hint=hint) as txn:
            txn.write("block", {"block_id": 10 ** 6, "inode_id": files[0],
                                "index": 7, "size": 0, "state": "UC"})
            old = store.table("block").scan_index("inode_id", files[1])
            txn.delete("block", (old[0]["block_id"],))
            values = files + [10 ** 7]
            if batched:
                rows = txn.ppis_many(("block", "replica"), "inode_id",
                                     values, EXCLUSIVE)
            else:
                rows = {t: [txn.ppis(t, "inode_id", v, EXCLUSIVE)
                            for v in values] for t in ("block", "replica")}
            cost = txn.cost.as_dict()
            txn.abort()
        out.append((rows, cost, store.total_row_ops,
                    store.locks.acquire_count, txn.overlay_scanned))
    assert out[0] == out[1]
    assert [len(r) for r in out[0][0]["replica"]] == [0, 3, 3, 0, 0]
    rows = out[0][0]["block"]
    assert [r["block_id"] for r in rows[0]] == [10 ** 6]
    assert rows[1] == [] and len(rows[2]) == 1
    assert rows[3] == [] and rows[4] == []
