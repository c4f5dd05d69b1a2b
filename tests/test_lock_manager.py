"""Striped LockManager: per-stripe mutexes, per-txn held-locks index
(O(locks held) release), waiter-safe entry reclamation, timeout behavior.
The concurrent request pipeline runs one thread per namenode against this
lock table, so these invariants are what test_batched_pipeline's
contention test leans on."""
import sys
import threading
import time

import pytest

from repro.core.store import (EXCLUSIVE, LockManager, LockTimeout,
                              READ_COMMITTED, SHARED)


def test_basic_shared_exclusive():
    lm = LockManager(timeout=0.05)
    lm.acquire(1, "inode", (1, "a"), SHARED)
    lm.acquire(2, "inode", (1, "a"), SHARED)     # shared coexists
    assert lm.held("inode", (1, "a")) == SHARED
    with pytest.raises(LockTimeout):
        lm.acquire(3, "inode", (1, "a"), EXCLUSIVE)
    lm.release_all(1)
    lm.release_all(2)
    lm.acquire(3, "inode", (1, "a"), EXCLUSIVE)
    assert lm.held("inode", (1, "a")) == EXCLUSIVE
    lm.release_all(3)
    assert lm.held("inode", (1, "a")) is None


def test_read_committed_takes_no_lock():
    lm = LockManager()
    lm.acquire(1, "inode", (1, "a"), READ_COMMITTED)
    assert lm.held("inode", (1, "a")) is None
    assert lm.held_count(1) == 0


def test_reentrant_and_upgrade():
    lm = LockManager(timeout=0.05)
    lm.acquire(1, "inode", (1, "a"), SHARED)
    lm.acquire(1, "inode", (1, "a"), EXCLUSIVE)  # sole holder may upgrade
    assert lm.held("inode", (1, "a")) == EXCLUSIVE
    assert lm.held_count(1) == 1                 # one row, one index entry
    lm.release_all(1)


def test_release_all_is_indexed_per_txn():
    """release_all walks only the txn's own held-locks index — other
    transactions' locks (any number of them) stay untouched."""
    lm = LockManager()
    n_other = 500
    for i in range(n_other):
        lm.acquire(100 + i, "inode", (i, "x"), EXCLUSIVE)
    lm.acquire(1, "inode", (9999, "mine"), EXCLUSIVE)
    lm.acquire(1, "block", (7,), SHARED)
    assert lm.held_count(1) == 2
    lm.release_all(1)
    assert lm.held_count(1) == 0
    assert lm.held("inode", (9999, "mine")) is None
    # everyone else still holds theirs
    for i in range(0, n_other, 97):
        assert lm.held("inode", (i, "x")) == EXCLUSIVE
    for i in range(n_other):
        lm.release_all(100 + i)
    assert all(not d for d in lm._locks)         # table fully reclaimed


def test_timeout_cleans_orphan_entry():
    lm = LockManager(timeout=0.02)
    lm.acquire(1, "inode", (1, "a"), EXCLUSIVE)
    with pytest.raises(LockTimeout):
        lm.acquire(2, "inode", (1, "a"), EXCLUSIVE)
    lm.release_all(1)
    assert all(not d for d in lm._locks)         # no leaked entries


def test_blocked_acquire_wakes_on_release():
    lm = LockManager(timeout=2.0)
    lm.acquire(1, "inode", (1, "a"), EXCLUSIVE)
    got = []

    def waiter():
        lm.acquire(2, "inode", (1, "a"), EXCLUSIVE)
        got.append(time.monotonic())
        lm.release_all(2)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    t0 = time.monotonic()
    lm.release_all(1)
    t.join(timeout=2.0)
    assert got and got[0] - t0 < 0.5             # woke promptly, not at
    assert not t.is_alive()                      # the 2s timeout


def test_striped_concurrency_no_lost_locks():
    """Many threads acquiring/releasing across many rows concurrently:
    every acquisition is exclusive-correct (a shared counter per row never
    sees two writers) and the table drains clean."""
    lm = LockManager(timeout=5.0, n_stripes=8)
    rows = [("inode", (i, "r")) for i in range(16)]
    owners = {pk: 0 for _t, pk in rows}
    errs = []

    def worker(txn_id: int) -> None:
        try:
            for k in range(40):
                tname, pk = rows[(txn_id * 7 + k) % len(rows)]
                lm.acquire(txn_id, tname, pk, EXCLUSIVE)
                owners[pk] += 1
                assert owners[pk] == 1, "two writers on one row!"
                owners[pk] -= 1
                lm.release_all(txn_id)
        except Exception as e:                    # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i + 1,))
               for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert all(not d for d in lm._locks)
    assert not lm._held


# --- acquire_many: the per-row acquire, one stripe mutex hold per call ---

ROWS = [(i, f"r{i}") for i in range(40)]


def _drained(lm):
    return all(not d for d in lm._locks) and not lm._held


def test_acquire_many_waits_for_a_conflicting_holder():
    lm = LockManager(timeout=2.0)
    lm.acquire(1, "inode", ROWS[7], EXCLUSIVE)
    got = []

    def waiter():
        lm.acquire_many(2, "inode", ROWS[:10], EXCLUSIVE)
        got.append(time.monotonic())

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    assert not got                               # blocked on ROWS[7]
    t0 = time.monotonic()
    lm.release_all(1)
    t.join(timeout=2.0)
    assert got and got[0] - t0 < 0.5 and not t.is_alive()
    assert lm.held_count(2) == 10
    assert lm.wait_count == 1 and lm.acquire_count == 11
    lm.release_all(2)
    assert _drained(lm)


def test_acquire_many_times_out_like_acquire():
    per_row, batched = LockManager(timeout=0.03), LockManager(timeout=0.03)
    for lm in (per_row, batched):
        lm.acquire(1, "inode", ROWS[3], EXCLUSIVE)
    with pytest.raises(LockTimeout):
        per_row.acquire(2, "inode", ROWS[3], EXCLUSIVE)
    with pytest.raises(LockTimeout):
        batched.acquire_many(2, "inode", [ROWS[3]], EXCLUSIVE)
    for lm in (per_row, batched):
        assert (lm.acquire_count, lm.wait_count) == (2, 1)
        assert lm.held_count(2) == 0
        assert lm.held("inode", ROWS[3]) == EXCLUSIVE
        lm.release_all(1)
        assert _drained(lm)


def test_acquire_many_keeps_what_it_took_before_a_timeout():
    lm = LockManager(timeout=0.03)
    lm.acquire(1, "inode", ROWS[5], EXCLUSIVE)
    with pytest.raises(LockTimeout):
        lm.acquire_many(2, "inode", ROWS[:10], EXCLUSIVE)
    # every row granted before the timeout is indexed for the abort
    taken = lm.held_count(2)
    assert taken <= 9
    assert taken == sum(lm.held("inode", pk) == EXCLUSIVE
                        for pk in ROWS[:10] if pk != ROWS[5])
    lm.release_all(2)
    lm.release_all(1)
    assert _drained(lm)


@pytest.mark.parametrize("first,then", [
    (SHARED, EXCLUSIVE), (EXCLUSIVE, SHARED), (SHARED, SHARED),
    (EXCLUSIVE, EXCLUSIVE)])
def test_acquire_many_reacquires_and_upgrades_like_acquire(first, then):
    """A transaction's own rows, taken again in another mode, end in the
    modes and counts the per-row calls leave."""
    per_row, batched = LockManager(timeout=0.05), LockManager(timeout=0.05)
    for lm in (per_row, batched):
        for pk in ROWS[:4]:
            lm.acquire(1, "inode", pk, first)
    for pk in ROWS[2:8]:
        per_row.acquire(1, "inode", pk, then)
    batched.acquire_many(1, "inode", ROWS[2:8], then)
    for lm in (per_row, batched):
        assert lm.held_count(1) == 8
        assert (lm.acquire_count, lm.wait_count) == (10, 0)
    assert ([batched.held("inode", pk) for pk in ROWS[:8]]
            == [per_row.held("inode", pk) for pk in ROWS[:8]])
    for lm in (per_row, batched):
        lm.release_all(1)
        assert _drained(lm)


def test_acquire_many_shares_shared_rows_and_counts_per_row():
    lm = LockManager(timeout=0.05)
    lm.acquire_many(1, "inode", ROWS[:6], SHARED)
    lm.acquire_many(2, "inode", ROWS[:6], SHARED)   # no wait: both shared
    lm.acquire_many(3, "inode", ROWS[:6], READ_COMMITTED)  # takes nothing
    assert (lm.acquire_count, lm.wait_count) == (12, 0)
    assert lm.held_count(1) == lm.held_count(2) == 6
    assert lm.held_count(3) == 0
    with pytest.raises(LockTimeout):
        lm.acquire_many(3, "inode", ROWS[4:6], EXCLUSIVE)
    assert lm.wait_count == 1
    for txn in (1, 2, 3):
        lm.release_all(txn)
    assert _drained(lm)


def test_a_per_row_waiter_wakes_on_a_batch_taken_row():
    """A row first locked by acquire_many has no condition until someone
    waits on it; a per-row acquire then makes one and is woken."""
    lm = LockManager(timeout=2.0)
    lm.acquire_many(1, "inode", ROWS[:3], EXCLUSIVE)
    got = []

    def waiter():
        lm.acquire(2, "inode", ROWS[1], EXCLUSIVE)
        got.append(time.monotonic())
        lm.release_all(2)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    t0 = time.monotonic()
    lm.release_all(1)
    t.join(timeout=2.0)
    assert got and got[0] - t0 < 0.5 and not t.is_alive()
    assert _drained(lm)


def test_acquire_many_under_contention_loses_no_lock():
    """Twelve threads take overlapping three-row batches, each in one
    order of (stripe, row) so that no two can deadlock, with a short
    switch interval: no row ever has two writers, every row counts one
    acquire, and the table drains clean."""
    lm = LockManager(timeout=5.0, n_stripes=8)
    rows = ROWS[:16]
    owners = {pk: 0 for pk in rows}
    errs = []

    def batch(txn_id, k):
        pks = {rows[(txn_id * 7 + k + 5 * j) % len(rows)] for j in range(3)}
        return sorted(pks, key=lambda pk: (lm._stripe(("inode", pk)), pk))

    def worker(txn_id: int) -> None:
        try:
            for k in range(40):
                pks = batch(txn_id, k)
                lm.acquire_many(txn_id, "inode", pks, EXCLUSIVE)
                for pk in pks:
                    owners[pk] += 1
                assert all(owners[pk] == 1 for pk in pks), "two writers!"
                for pk in pks:
                    owners[pk] -= 1
                lm.release_all(txn_id)
        except Exception as e:                    # pragma: no cover
            errs.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i + 1,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errs
    assert lm.acquire_count == sum(len(batch(i + 1, k)) for i in range(12)
                                   for k in range(40))
    assert _drained(lm)
