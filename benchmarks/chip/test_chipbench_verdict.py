"""The comparison that decides ``correct``. Each test drives the rest of a
run (``run_cell.run``: load, warm-up, open-loop window, judge) on a cell
cut to CPU size, skipping only the harness's look for a chip:

* the program as it stands comes out correct;
* with the timed path broken underneath -- an acknowledged write that
  never lands, half of every batch left out and answered from the rest,
  an answer altered where it is produced -- ``correct`` comes out false;
* the control (the reference with reads served from a lagging replica)
  fails the judge;
* the reference agrees with the program replayed one op at a time.

The cell has one chip, so there is no exchange between chips to leave
out.
"""
import pytest

from control import run_control
from nsplan import NamespacePlan
from refmodel import ANSWER_ERRORS, RefFS, normalize
from run_cell import run
from tinycell import TINY_WARMUP_S, tiny

PEAK = {"hbm_bytes_per_s": 819e9}
SEED = 2 ** 31 + 77


@pytest.fixture(autouse=True)
def _no_kernel_bucket_warmup(monkeypatch):
    """Compiling every kernel bucket in the Pallas interpreter would take
    minutes on the CPU; the window compiles what it needs."""
    import warmup
    monkeypatch.setattr(warmup, "warm_kernels", lambda *a, **k: None)


def _run(cell_name="spotify-1m.steady", **kw):
    cell = tiny(cell_name, **kw)
    return run(cell, SEED, 1.5, False, PEAK, warmup_s=TINY_WARMUP_S)


@pytest.mark.parametrize("rate", [150.0, 600.0])
def test_program_as_it_stands_is_correct(rate):
    res = _run(rate=rate)
    assert res["attempted"] > 50
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert all(c["limit"] == 0 for c in res["checks"].values())


def test_acknowledged_write_that_never_lands(monkeypatch):
    from repro.core.fs import HopsFSOps
    monkeypatch.setattr(HopsFSOps, "create_apply",
                        lambda self, txn, parent, target, name, path, **kw:
                        10 ** 9)
    res = _run(rate=300.0)
    assert not res["correct"]
    assert res["checks"]["state_mismatches"]["value"] > 0


def test_half_of_each_batch_left_out(monkeypatch):
    from repro.core.namenode import Namenode
    real = Namenode.execute_batch

    def half(self, wops, hints=None):
        keep = (len(wops) + 1) // 2
        out = real(self, wops[:keep],
                   None if hints is None else hints[:keep])
        return out + [out[i % keep] for i in range(len(wops) - keep)]
    monkeypatch.setattr(Namenode, "execute_batch", half)
    res = _run(rate=800.0)
    assert not res["correct"]
    assert res["checks"]["op_mismatches"]["value"] > 0


def test_answer_altered_where_produced(monkeypatch):
    from repro.core.fs import HopsFSOps
    real = HopsFSOps.listing_payload
    monkeypatch.setattr(HopsFSOps, "listing_payload",
                        lambda self, txn, target: real(self, txn, target)[1:])
    res = _run(rate=300.0)
    assert not res["correct"]
    assert res["checks"]["op_mismatches"]["value"] > 0


@pytest.mark.parametrize("rate", [400.0, 800.0])
def test_control_fails_the_judge(rate):
    c = tiny("spotify-1m.steady", trees=4, rate=rate)
    out = run_control(c.config, c.traffic, SEED, 2.0, lag=0.5,
                      warmup_s=TINY_WARMUP_S)
    assert out["dispatched"] > 300
    assert out["op_mismatches"] > 0


def test_reference_matches_sequential_program():
    from chipload import build_cluster
    from repro.core import DFSClient
    from workgen import make_generator
    c = tiny("spotify-1m.steady", trees=3)
    mix = c.traffic["mix"] + [["complete", 1.0, 0.0], ["du", 1.0, 0.5]]
    plan = NamespacePlan(c.config["namespace"]["parts"])
    gen = make_generator(c.config, {"mix": mix}, plan)
    _, ops = gen.schedule("1", 300.0, 8.0)
    store, cluster = build_cluster(c.config, plan)
    st = DFSClient(cluster).run_trace(ops, planned=False, batch_size=1)
    ref = RefFS(plan)
    compared = 0
    for w, o in zip(ops, st.outcomes):
        got = normalize(w.op, o.ok, o.error,
                        o.result.value if o.ok else None)
        assert got[0] is None or got[0] in ANSWER_ERRORS, got
        assert got == ref.apply(w.op, w.path, w.path2, dict(w.args)), w
        compared += 1
    assert compared == len(ops) > 2000
    assert store.table("inode").n_rows == ref.n_inodes
    assert store.table("block").n_rows == ref.n_blocks
