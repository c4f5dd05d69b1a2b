"""Roofline work counts come from the work asked of a kernel (probes),
never from table capacity: the same traffic on a store whose index is
twice as large counts the same bytes. Unknown devices have no peaks."""
import json
from pathlib import Path

import pytest

from chipload import build_cluster
from nsplan import NamespacePlan
from run_cell import counters, kernel_probes, observe_planner
from workcount import peaks, pkval_bytes, roofline_percent

HERE = Path(__file__).resolve().parent


def _config():
    cfg = json.loads((HERE / "configs" / "spotify-1m.json").read_text())
    for p in cfg["namespace"]["parts"]:
        p["trees"] = 2
    return cfg


def _cluster(pad: int):
    """The configuration's cluster; with ``pad`` extra rows put and
    deleted again, so the index is larger while the namespace is the
    same."""
    from repro.core.tables import make_inode
    cfg = _config()
    store, cluster = build_cluster(cfg, NamespacePlan(
        cfg["namespace"]["parts"]))
    t = store.table("inode")
    ids = cluster.namenodes[0].ops.inode_ids
    for i in range(pad):
        t.put(make_inode(ids.next_id(), 999_999_999, f"pad{i}", False))
    for i in range(pad):
        t.delete((999_999_999, f"pad{i}"))
    return store, cluster


def _reads(n=64):
    from repro.core.ops_registry import WorkloadOp
    from workgen import TrafficGenerator
    cfg = _config()
    g = TrafficGenerator(NamespacePlan(cfg["namespace"]["parts"]),
                         [["read", 1.0, 0.0]], 1.1, 7)
    return [WorkloadOp("read", g.sample_file()) for _ in range(n)]


def _pkval_probes(store, cluster, wops):
    from repro.core import DFSClient
    client = DFSClient(cluster)
    reports = []
    with observe_planner(reports):
        for _ in range(2):          # the first window warms the caches
            c0 = counters(cluster, store, kernel_probes(), reports)
            client.run_trace(wops, planned=True, concurrent=True,
                             adaptive=False, window=len(wops))
            c1 = counters(cluster, store, kernel_probes(), reports)
    return ((c1["planner_pkval_probes"] - c0["planner_pkval_probes"])
            + (c1["nn_pkval_probes"] - c0["nn_pkval_probes"]))


def test_pkval_count_does_not_change_with_index_capacity():
    wops = _reads()
    small = _cluster(0)
    large = _cluster(40_000)
    cap_s = small[0].table("inode").hindex.cap
    cap_l = large[0].table("inode").hindex.cap
    assert cap_l >= 2 * cap_s
    probes_s = _pkval_probes(*small, wops)
    probes_l = _pkval_probes(*large, wops)
    assert probes_s > 0
    assert probes_s == probes_l
    assert pkval_bytes(probes_s) == pkval_bytes(probes_l) == 24 * probes_s


def test_roofline_share_and_unknown_device():
    peak = peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert roofline_percent(819_000, 1e-6, peak) == pytest.approx(100.0)
    assert roofline_percent(0, 1e-3, peak) is None
    assert roofline_percent(1000, None, peak) is None
    with pytest.raises(KeyError):
        peaks("TPU v99 imaginary")
