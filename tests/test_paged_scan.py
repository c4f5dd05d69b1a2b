"""The paged partition-pruned scan (``Table.scan_index_page``,
``Transaction.ppis_page``) that the subtree protocol quiesces and deletes
directories with: a sweep returns every child once on both stores, rows
deleted between pages never move the cursor, and a page locks only its
own rows."""
import pytest

from repro.core import MetadataStore, format_fs
from repro.core.columnar import ColumnarMetadataStore
from repro.core.store import EXCLUSIVE, PagedBucket
from repro.core.tables import ROOT_ID, make_inode
from repro.core.transactions import Transaction

STORES = {"dict": MetadataStore, "columnar": ColumnarMetadataStore}
DIR_ID = 10


def _store(kind, n_children=300, *, other_dir=True):
    """A store holding directory ``DIR_ID`` under the root with
    ``n_children`` files, and a second directory of 5 files beside it."""
    store = STORES[kind](n_datanodes=4)
    format_fs(store)
    t = store.table("inode")
    t.put(make_inode(DIR_ID, ROOT_ID, "big", True))
    for i in range(n_children):
        t.put(make_inode(1000 + i, DIR_ID, f"f{i:05d}", False))
    if other_dir:
        t.put(make_inode(11, ROOT_ID, "small", True))
        for i in range(5):
            t.put(make_inode(5000 + i, 11, f"g{i}", False))
    return store


def _sweep(table, limit, value=DIR_ID, between=None):
    """Every page of ``value``'s children; ``between(rows)`` runs after
    each page."""
    pages, after = [], None
    while True:
        rows, after = table.scan_index_page("parent_id", value, after, limit)
        pages.append(rows)
        if between is not None:
            between(rows)
        if after is None:
            return pages


@pytest.mark.parametrize("limit", [1, 7, 1000])
def test_sweep_returns_each_child_once_on_both_stores(limit):
    got = {}
    for kind in STORES:
        t = _store(kind).table("inode")
        pages = _sweep(t, limit)
        ids = [r["id"] for page in pages for r in page]
        assert len(ids) == len(set(ids)) == 300
        assert all(len(p) <= limit for p in pages)
        assert len(pages) == max(1, -(-300 // limit))
        assert set(ids) == {r["id"] for r in t.scan_index("parent_id",
                                                           DIR_ID)}
        got[kind] = set(ids)
    assert got["dict"] == got["columnar"]


@pytest.mark.parametrize("kind", sorted(STORES))
@pytest.mark.parametrize("limit", [1, 7, 64])
def test_rows_deleted_between_pages_do_not_move_the_cursor(kind, limit):
    t = _store(kind).table("inode")
    seen = []

    def delete_page(rows):
        seen.extend(r["id"] for r in rows)
        for r in rows:
            assert t.delete((r["parent_id"], r["name"]))

    _sweep(t, limit, between=delete_page)
    assert sorted(seen) == list(range(1000, 1300))
    assert t.scan_index("parent_id", DIR_ID) == []


@pytest.mark.parametrize("kind", sorted(STORES))
def test_deleting_returned_rows_and_others_keeps_the_sweep_whole(kind):
    """Deletes of rows a page returned, plus every third row not yet
    returned: the sweep returns each survivor once and nothing deleted
    before its page came (holes, and compactions of the bucket)."""
    t = _store(kind).table("inode")
    gone, seen = set(), []

    def delete_some(rows):
        seen.extend(r["id"] for r in rows)
        for r in rows:
            t.delete((r["parent_id"], r["name"]))
        ahead = [r for r in t.scan_index("parent_id", DIR_ID)
                 if r["id"] % 3 == 0][:2]
        for r in ahead:
            gone.add(r["id"])
            t.delete((r["parent_id"], r["name"]))

    _sweep(t, 9, between=delete_some)
    assert len(seen) == len(set(seen))
    assert set(seen) | gone == set(range(1000, 1300))
    assert not set(seen) & gone


@pytest.mark.parametrize("kind", sorted(STORES))
def test_updated_rows_keep_their_place(kind):
    """Rows rewritten in place (an mtime tick) between pages, returned
    ones and others: no row comes twice or goes missing."""
    t = _store(kind).table("inode")
    seen = []

    def touch(rows):
        seen.extend(r["id"] for r in rows)
        ahead = t.scan_index("parent_id", DIR_ID)[-3:]
        for r in rows + ahead:
            t.put(dict(t.get((r["parent_id"], r["name"])), mtime=7.0))

    _sweep(t, 7, between=touch)
    assert sorted(seen) == list(range(1000, 1300))


@pytest.mark.parametrize("kind", sorted(STORES))
def test_page_locks_only_its_own_rows(kind):
    store = _store(kind)
    t = store.table("inode")
    pks = {r["id"]: (r["parent_id"], r["name"])
           for r in t.scan_index("parent_id", DIR_ID)}
    with Transaction(store, partition_hint=("inode", DIR_ID)) as txn:
        rows, after = txn.ppis_page("inode", "parent_id", DIR_ID, EXCLUSIVE,
                                    limit=7, projection=("id", "name"))
        assert after is not None and len(rows) == 7
        assert set(rows[0]) == {"id", "name"}
        assert txn.cost.ppis == 1 and txn.cost.round_trips == 1
        mine = {pks[r["id"]] for r in rows}
        for pk in pks.values():
            assert store.locks.held("inode", pk) == (
                EXCLUSIVE if pk in mine else None)
        rows2, _ = txn.ppis_page("inode", "parent_id", DIR_ID, EXCLUSIVE,
                                 after=after, limit=7)
        assert not {r["id"] for r in rows} & {r["id"] for r in rows2}
        assert txn.cost.ppis == 2
    assert all(store.locks.held("inode", pk) is None for pk in pks.values())


@pytest.mark.parametrize("kind", sorted(STORES))
def test_small_and_missing_directories_take_one_page(kind):
    t = _store(kind).table("inode")
    rows, after = t.scan_index_page("parent_id", 11, None, 5)
    assert len(rows) == 5 and after is None
    assert t.scan_index_page("parent_id", 999, None, 5) == ([], None)
    with pytest.raises(ValueError, match="partition key"):
        t.scan_index_page("id", DIR_ID, None, 5)


def test_bucket_keeps_insertion_order_through_compaction():
    b = PagedBucket()
    for k in range(10):
        b.add((k,))
    for k in range(0, 8):
        b.discard((k,))           # the 6th hole of 10 compacts the lists
    assert list(b) == [(8,), (9,)]
    assert len(b.keys) == 4 and b.holes == 2
    b.add((3,))                   # re-added: a new number, at the end
    assert list(b) == [(8,), (9,), (3,)]
    first, after = b.page(None, 2)
    assert first == [(8,), (9,)]
    b.discard((8,))
    b.discard((9,))
    assert b.page(after, 2) == ([(3,)], None)
    assert list(b) == [(3,)] and len(b) == 1
    with pytest.raises(ValueError):
        b.page(None, 0)


def test_index_buckets_compare_equal_across_stores():
    d, c = _store("dict"), _store("columnar")
    assert d.table("inode").idx == c.table("inode").idx
