"""The subtree protocol over paged child scans (§6): a directory larger
than a page is quiesced and deleted page by page with bounded memory,
with the answers, crash points and store state of the plain reference
and of the legacy full-scan engine; its counters, spans and the
benchmark readers that read them."""
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro.core import (MetadataStore, NamenodeCluster, WorkloadOp,
                        format_fs, materialize_big_dir)
from repro.core.columnar import ColumnarMetadataStore
from repro.core.subtree import SubtreeOps
from repro.core.tables import ROOT_ID

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
STORES = {"dict": MetadataStore, "columnar": ColumnarMetadataStore}
BATCH = 50


def _cluster(kind, n_files=730, *, batch=BATCH, incremental=True):
    """One namenode over a store holding /big: ``n_files`` files and two
    subdirectories of 120 and 3 files."""
    store = STORES[kind](n_datanodes=4)
    format_fs(store)
    nn = NamenodeCluster(store, 1).namenodes[0]
    materialize_big_dir(nn, "/big", n_files)
    materialize_big_dir(nn, "/big/sub", 120, file_prefix="s")
    materialize_big_dir(nn, "/big/sub/leaf", 3, file_prefix="l")
    nn.subtree.batch_size = batch
    nn.subtree.incremental = incremental
    return store, nn


def _inodes_under(store, path):
    t = store.table("inode")
    cur = ROOT_ID
    for name in path.strip("/").split("/"):
        row = t.get((cur, name))
        if row is None:
            return 0
        cur = row["id"]
    n, stack = 1, [cur]
    while stack:
        kids = t.scan_index("parent_id", stack.pop())
        n += len(kids)
        stack.extend(k["id"] for k in kids if k["is_dir"])
    return n


def _no_orphans(store):
    t = store.table("inode")
    ids = {r["id"] for part in t.parts for r in part.values()}
    return all(r["parent_id"] in ids or r["id"] == ROOT_ID
               for part in t.parts for r in part.values())


@pytest.mark.parametrize("kind", sorted(STORES))
def test_paged_delete_holds_a_page_and_a_chunk(kind):
    store, nn = _cluster(kind)
    total = _inodes_under(store, "/big")
    assert total == 1 + 730 + 2 + 120 + 3
    res = nn.invoke(WorkloadOp("delete_subtree", "/big", on_dir=True))
    st = nn.subtree.last_stats
    assert res.value == {"deleted": total, "crashed": False}
    assert store.table("inode").get((ROOT_ID, "big")) is None
    assert store.table("inode").n_rows == 1
    assert st["peak_frontier"] <= 2 * BATCH + 1
    # 730 children in pages of 50, 122 in 3, 3 in 1
    assert st["pages"] == 15 + 3 + 1
    assert st["scanned"] == total - 1 and st["deleted"] == total
    assert st["scan_s"] > 0 and st["commit_s"] > 0
    assert nn.subtree.treeagg_mismatches == 0


@pytest.mark.parametrize("op", ["delete_subtree", "chmod_subtree"])
def test_last_stats_carry_pages_deleted_and_host_seconds(op):
    store, nn = _cluster("dict")
    args = {"perm": 0o700} if op == "chmod_subtree" else {}
    nn.invoke(WorkloadOp(op, "/big", args=args, on_dir=True))
    st = nn.subtree.last_stats
    assert st["pages"] == 19
    assert st["scanned"] == 855
    assert st["scan_s"] > 0
    if op == "delete_subtree":
        assert st["deleted"] == 856 and st["chunks"] > 0
        assert st["commit_s"] > 0
    else:
        assert st["deleted"] == 0 and st["chunks"] == 0
        assert st["commit_s"] == 0.0
        assert store.table("inode").get((ROOT_ID, "big"))["perm"] == 0o700


@pytest.mark.parametrize("kind", sorted(STORES))
@pytest.mark.parametrize("crash_after", [1, 4, 9])
def test_crash_after_chunks_leaves_a_consistent_smaller_tree(kind,
                                                             crash_after):
    store, nn = _cluster(kind)
    total = _inodes_under(store, "/big")
    nn.subtree.crash_after_batches = crash_after
    res = nn.invoke(WorkloadOp("delete_subtree", "/big", on_dir=True))
    assert res.value["crashed"]
    assert res.value["deleted"] == crash_after * BATCH
    assert nn.subtree.last_stats["deleted"] == crash_after * BATCH
    left = _inodes_under(store, "/big")
    assert left == total - crash_after * BATCH
    assert _no_orphans(store)
    # the flag is the crashed namenode's; once it is reclaimed a retry
    # deletes what is left
    root = store.table("inode").get((ROOT_ID, "big"))
    assert root["subtree_lock"] == nn.ops.nn_id
    retry = SubtreeOps(nn.ops, batch_size=BATCH)
    nn.ops._is_nn_alive = lambda nid: False
    res = retry.delete_subtree("/big")
    assert res.value == {"deleted": left, "crashed": False}
    assert store.table("inode").n_rows == 1


@pytest.mark.parametrize("kind", sorted(STORES))
@pytest.mark.parametrize("op,args", [
    ("chmod_subtree", {"perm": 0o700}),
    ("chown_subtree", {"owner": "alice"}),
    ("delete_subtree", {}),
])
def test_paged_engine_matches_the_legacy_engine(kind, op, args):
    """The same op on two equal stores, streamed and legacy: the same
    answer, store, rows scanned, pages and round trips."""
    out = []
    for incremental in (True, False):
        store, nn = _cluster(kind, incremental=incremental)
        res = nn.invoke(WorkloadOp(op, "/big", args=args, on_dir=True))
        st = nn.subtree.last_stats
        out.append((res.value, store.dump_state(),
                    st["scanned"], st["pages"], res.cost.ppis))
    assert out[0] == out[1]


@pytest.mark.parametrize("kind", sorted(STORES))
def test_paged_delete_matches_the_plain_reference(kind):
    sys.path.insert(0, str(CHIP))
    from refmodel import RefFS
    from nsplan import NamespacePlan
    parts = [{"kind": "trees", "prefix": "big", "trees": 1, "depth": 2,
              "dirs_per_dir": 2, "files_per_dir": 130, "name_len": 12,
              "traffic": False}]
    plan = NamespacePlan(parts)
    from chipload import build_cluster
    config = {"cluster": {"namenodes": 1, "partitions": 64, "datanodes": 4,
                          "replication": 2}}
    store, cluster = build_cluster(config, plan)
    nn = cluster.namenodes[0]
    nn.subtree.batch_size = 40
    ref = RefFS(plan)
    res = nn.invoke(WorkloadOp("delete_subtree", "/big", on_dir=True))
    err, (want, crashed) = ref.apply("delete_subtree", "/big", None, {})
    assert err is None and not crashed
    assert res.value == {"deleted": want, "crashed": False}
    assert want == 3 + 3 * 130
    assert nn.subtree.last_stats["peak_frontier"] <= 2 * 40 + 1 + 2
    assert store.table("inode").n_rows == 1


def test_subtree_spans_are_in_the_profile(tmp_path):
    from jax.profiler import ProfileData
    store, nn = _cluster("dict", 120)
    with jax.profiler.trace(str(tmp_path)):
        nn.invoke(WorkloadOp("chmod_subtree", "/big", args={"perm": 0o700},
                             on_dir=True))
        nn.invoke(WorkloadOp("delete_subtree", "/big", on_dir=True))
    (pb,) = Path(tmp_path).rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines for ev in line.events}
    assert {"namenode.subtree_lock", "namenode.subtree_scan",
            "namenode.subtree_chunk"} <= names


# ---------------------------------------------------------------------------
# the benchmark's readers of the counters, and the bigdir-1m.rmr cell cut
# to a CPU run
# ---------------------------------------------------------------------------

def _reader(name):
    sys.path.insert(0, str(CHIP))
    from cellspec import load_reader, reader_path
    return load_reader(name), reader_path(name)


def _ctx(entries, scheduled=((0, "delete_subtree", "/big"),)):
    return SimpleNamespace(scheduled=list(scheduled), subtree=entries)


_DELETE = {"op": "delete_subtree", "path": "/big", "waves": 1,
           "scanned": 2000, "peak_frontier": 1001, "chunks": 3, "pages": 2,
           "deleted": 2001, "scan_s": 0.5, "commit_s": 2.001,
           "seconds": 2.6}
_CHOWN = dict(_DELETE, op="chown_subtree", path="/w000/d", scan_s=9.0,
              commit_s=0.0, scanned=18, deleted=0)


@pytest.mark.parametrize("metric,want", [
    ("subtree.scan_us_per_row", 1e6 * 0.5 / 2000),
    ("subtree.commit_us_per_row", 1e6 * 2.001 / 2001),
])
def test_subtree_readers(metric, want):
    reader, path = _reader(metric)
    assert path.name == f"{metric}.py"
    assert reader.read(_ctx([_CHOWN, _DELETE])) == pytest.approx(want)
    # no scheduled delete ran, or it ran on a program without the counters
    assert reader.read(_ctx([_CHOWN])) is None
    assert reader.read(_ctx([_DELETE], scheduled=())) is None
    old = {k: v for k, v in _DELETE.items()
           if k not in ("pages", "deleted", "scan_s", "commit_s")}
    assert reader.read(_ctx([old])) is None


def test_idle_share_of_the_rmr_cell_reads_the_device_trace():
    reader, path = _reader("device.idle_share.rmr")
    assert path.name == "device.idle_share.py"
    ctx = SimpleNamespace(trace={"busy_s": 1.0, "window_s": 80.0})
    assert reader.read(ctx) == pytest.approx(98.75)


@pytest.fixture
def _tiny_rmr_run(monkeypatch, capsys):
    """bigdir-1m.rmr cut to a CPU run (2,000 files under /big), with the
    namenodes' subtree pages and chunks of 100 rows: the result and the
    log line of the scheduled delete."""
    sys.path.insert(0, str(CHIP))
    import warmup
    from run_cell import run
    from tinycell import TINY_WARMUP_S, TINY_WINDOW_S, tiny
    # compiling every kernel bucket in the Pallas interpreter would take
    # minutes on the CPU; the window compiles what it needs
    monkeypatch.setattr(warmup, "warm_kernels", lambda *a, **k: None)
    real_init = SubtreeOps.__init__

    def init(self, *args, **kw):
        real_init(self, *args, **kw)
        self.batch_size = 100
    monkeypatch.setattr(SubtreeOps, "__init__", init)
    cell = tiny("bigdir-1m.rmr")
    assert cell.config["namespace"]["parts"][-1]["files_per_dir"] == 2000
    assert cell.traffic["scheduled"][0]["at_s"] == 0.0
    res = run(cell, 2 ** 31 + 1606, TINY_WINDOW_S, False,
              {"hbm_bytes_per_s": 819e9}, warmup_s=TINY_WARMUP_S)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("scheduled ")]
    return res, lines


def test_tiny_rmr_cell_is_correct_with_paged_subtree_ops(_tiny_rmr_run):
    res, lines = _tiny_rmr_run
    assert res["correct"], res["checks"]
    assert res["checks"]["op_mismatches"]["value"] == 0
    assert set(res["metrics"]) == {"setup_s", "p99_ms"}
    assert len(lines) == 1
    line = lines[0]
    assert line.startswith("scheduled delete_subtree /big: due 0.000000 s")
    assert ", ok; " in line
    stats = dict(kv.split(" ", 1) for kv in line.split(", ok; ")[1]
                 .split("; "))
    # the reference's answer (op_mismatches 0): the files and /big itself
    assert int(stats["deleted"]) == 2001
    assert int(stats["peak_frontier"]) <= 2 * 100 + 1
    assert int(stats["pages"]) == 20 and int(stats["chunks"]) == 21
