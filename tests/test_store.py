"""Unit tests: partitioned store, locks, transactions, cost accounting."""
import pytest

from repro.core import (EXCLUSIVE, READ_COMMITTED, SHARED, MetadataStore,
                        NodeGroupDown, Transaction, format_fs)
from repro.core.store import LockManager, _hash_key, hash_partitions
from repro.core.tables import make_inode


@pytest.fixture
def store():
    s = MetadataStore(n_datanodes=4, replication=2, n_partitions=16)
    format_fs(s)
    return s


def test_partitioning_is_deterministic(store):
    t = store.table("inode")
    assert t.partition_of(42) == t.partition_of(42)
    # children co-located: same parent id -> same partition (paper §4.2)
    parts = {t.partition_of(7) for _ in range(10)}
    assert len(parts) == 1


@pytest.mark.parametrize("n_partitions", [1, 7, 64])
@pytest.mark.parametrize("values", [
    [0, 1, 2, 3, 1000, 123456789],
    [-1, -2, -(2 ** 31), -(2 ** 63), -(2 ** 63) + 1],
    [2 ** 32 - 1, 2 ** 32, 2 ** 32 + 1, 2 ** 31, 2 ** 31 - 1],
    [2 ** 63 - 1, 2 ** 63 - 2, 2 ** 62 + 12345],
    [2 ** 63, 2 ** 64 + 5, 1],                 # past int64: per value
    [7, "a", (1, "b"), True, 7.5],             # not all ints: per value
])
def test_hash_partitions_is_hash_key_per_value(values, n_partitions):
    assert hash_partitions(values, n_partitions) == \
        [_hash_key(v) % n_partitions for v in values]


def test_children_on_same_shard(store):
    t = store.table("inode")
    for i in range(50):
        t.put(make_inode(100 + i, 7, f"f{i}", False))
    part = t.partition_of(7)
    rows = t.scan_partition(part, lambda r: r["parent_id"] == 7)
    assert len(rows) == 50


def test_file_metadata_colocated(store):
    """Blocks/replicas of one file share a shard (distribution-aware read)."""
    bt, rt = store.table("block"), store.table("replica")
    assert bt.partition_of(12345) == rt.partition_of(12345)


def test_node_groups_and_failures(store):
    assert store.n_groups == 2
    store.fail_datanode(0)
    assert store.available()          # replica in the group survives
    store.fail_datanode(1)
    assert not store.available()      # group 0 fully down
    with pytest.raises(NodeGroupDown):
        for p in range(store.n_partitions):
            store.check_available(p)
    store.recover_datanode(0)
    assert store.available()


def test_transaction_commit_and_abort(store):
    txn = Transaction(store, partition_hint=("inode", 1))
    txn.write("inode", make_inode(50, 1, "a", True))
    txn.commit()
    assert store.table("inode").get((1, "a")) is not None

    txn2 = Transaction(store, partition_hint=("inode", 1))
    txn2.write("inode", make_inode(51, 1, "b", True))
    txn2.abort()
    assert store.table("inode").get((1, "b")) is None


def test_row_locks_block_conflicts(store):
    lm = LockManager(timeout=0.05)
    lm.acquire(1, "inode", (1, "x"), EXCLUSIVE)
    from repro.core import LockTimeout
    with pytest.raises(LockTimeout):
        lm.acquire(2, "inode", (1, "x"), SHARED)
    lm.release_all(1)
    lm.acquire(2, "inode", (1, "x"), SHARED)   # now fine
    lm.acquire(3, "inode", (1, "x"), SHARED)   # shared compatible


def test_batch_counts_one_round_trip(store):
    txn = Transaction(store, partition_hint=("inode", 1))
    txn.read_batch([("inode", (0, ""), READ_COMMITTED)] * 5)
    assert txn.cost.batches == 1
    assert txn.cost.batch_rows == 5
    assert txn.cost.round_trips == 1
    txn.abort()


def test_ppis_vs_is_cost(store):
    t = store.table("inode")
    for i in range(10):
        t.put(make_inode(200 + i, 9, f"c{i}", False))
    txn = Transaction(store, partition_hint=("inode", 9))
    txn.ppis("inode", "parent_id", 9)
    assert txn.cost.ppis == 1 and txn.cost.is_scans == 0
    txn.index_scan("inode", "parent_id", 9)
    assert txn.cost.is_scans == 1
    txn.abort()


def test_distribution_awareness_locality(store):
    """Hinted transactions read hint-partition rows locally (§2.2)."""
    t = store.table("inode")
    t.put(make_inode(300, 11, "kid", False))
    txn = Transaction(store, partition_hint=("inode", 11))
    txn.ppis("inode", "parent_id", 11)
    assert txn.cost.local_rt == 1 and txn.cost.remote_rt == 0
    txn.abort()
    txn2 = Transaction(store, partition_hint=("inode", 11),
                       distribution_aware=False)
    txn2.ppis("inode", "parent_id", 11)
    # round-robin coordinator: locality is accidental at best
    assert txn2.cost.local_rt + txn2.cost.remote_rt == 1
    txn2.abort()


def test_memory_accounting(store):
    before = store.memory_bytes()
    store.table("inode").put(make_inode(400, 1, "m", False))
    after = store.memory_bytes()
    assert after - before == 296 * store.replication
