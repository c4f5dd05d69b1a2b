"""Scheduled operations: fixed ops that a traffic file puts on named
directories of ``traffic: false`` namespace parts, at fixed offsets of
the warm-up or the window.

* a cell that schedules nothing offers the tapes it offered before
  traffic files could schedule ops (hashes in ``testdata/``);
* a scheduled op falls due at its offset under every seed's order, and
  the rest of the tape is the unscheduled one;
* an entry the harness cannot send as asked is refused;
* a tiny cell with a 2,000-file directory deleted in its window comes
  out correct, and not correct when phase 3 leaves a child behind.
"""
import hashlib
import json
from pathlib import Path

import pytest

from cellspec import find_cell, load_benchmark
from nsplan import NamespacePlan
from run_cell import BLOCK_S, WARMUP_S, WARMUP_SEED, WORK_SEED, run
from tinycell import TINY_WARMUP_S, TINY_WINDOW_S, shrink
from workgen import make_generator

HERE = Path(__file__).resolve().parent
TAPES = json.loads((HERE / "testdata" / "steady_tapes.json").read_text())
SCHEDULED_CELL = json.loads(
    (HERE / "testdata" / "scheduled_cell.json").read_text())
PEAK = {"hbm_bytes_per_s": 819e9}


def tape_hash(due, ops) -> str:
    h = hashlib.sha256()
    for d, o in zip(due, ops):
        h.update(repr((d, o.op, o.path, o.path2, o.on_dir,
                       sorted(o.args.items()), o.deadline,
                       o.tenant)).encode())
    return h.hexdigest()


def _key(o):
    return (o.op, o.path, o.path2, o.on_dir, sorted(o.args.items()))


def _tapes(gen, rate, seed, seconds, warmup_s=WARMUP_S):
    """The warm-up's and the window's tapes, drawn as ``run_cell.run``
    draws them."""
    warm = gen.schedule(WARMUP_SEED, rate, warmup_s, phase="warmup")
    window = gen.schedule(str(seed), rate, seconds, work_seed=WORK_SEED,
                          block_s=BLOCK_S, phase="window")
    return {"warmup": warm, "window": window}


@pytest.mark.parametrize("seed", sorted(TAPES["tapes"]))
def test_steady_tapes_are_unchanged(seed):
    cell = find_cell("spotify-1m.steady", trace=False)
    gen = make_generator(cell.config, cell.traffic)
    assert gen.scheduled == []
    got = _tapes(gen, float(cell.traffic["rate_ops_per_s"]), seed,
                 float(load_benchmark()["run_seconds"]))
    for phase, want in TAPES["tapes"][seed].items():
        due, ops = got[phase]
        assert len(ops) == want["ops"], phase
        assert tape_hash(due, ops) == want["sha256"], phase


def _scheduled_cell(trees=4, files=2000, scheduled=None):
    """spotify-1m.steady cut to ``trees`` trees, with the fixture's
    ``traffic: false`` directory of ``files`` files."""
    cell = find_cell("spotify-1m.steady", trace=False)
    cell.config["namespace"]["parts"] = [
        dict(p, trees=min(p["trees"], trees))
        for p in cell.config["namespace"]["parts"]] + [
        dict(p, files_per_dir=files) for p in SCHEDULED_CELL["parts"]]
    cell.traffic = dict(cell.traffic, scheduled=SCHEDULED_CELL["scheduled"]
                        if scheduled is None else scheduled)
    return cell


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 17, 2 ** 31 + 4242])
def test_scheduled_op_falls_due_at_its_offset(seed):
    entries = SCHEDULED_CELL["scheduled"] + [
        {"op": "chmod_subtree", "path": "/big", "at_s": 2.5,
         "phase": "warmup", "args": {"perm": 0o700}}]
    cell = _scheduled_cell(scheduled=entries)
    plain = dict(cell.traffic, scheduled=[])
    rate = float(cell.traffic["rate_ops_per_s"])
    got = _tapes(make_generator(cell.config, cell.traffic), rate, seed, 40.0)
    want = _tapes(make_generator(cell.config, plain), rate, seed, 40.0)
    for e in entries:
        due, ops = got[e["phase"]]
        k = [o.path for o in ops].index(e["path"])
        assert due[k] == e["at_s"]
        assert due[k - 1] <= e["at_s"] < due[k + 1]
        assert (ops[k].op, ops[k].on_dir, ops[k].args) == \
            (e["op"], True, e["args"])
        w_due, w_ops = want[e["phase"]]
        assert due[:k] + due[k + 1:] == w_due
        assert [_key(o) for o in ops[:k] + ops[k + 1:]] == \
            [_key(o) for o in w_ops]


def _file_of_big():
    plan = NamespacePlan(SCHEDULED_CELL["parts"])
    return "/big/" + plan.trees[0].file_name(0)


@pytest.mark.parametrize("change,match", [
    ({"path": "/nowhere"}, "not a directory"),
    ({"path": _file_of_big()}, "not a directory"),
    ({"path": "/w000"}, "traffic: false"),
    ({"path": "/"}, "traffic: false"),
    ({"op": "truncate_subtree"}, "no op 'truncate_subtree'"),
    ({"phase": "cooldown"}, "phase"),
    ({"at_s": 40.0}, "outside"),
    ({"at_s": -0.5}, "outside"),
], ids=["missing-path", "file-path", "traffic-part", "root", "unknown-op",
        "unknown-phase", "at-window-end", "negative-offset"])
def test_scheduled_entry_is_refused(change, match):
    entry = dict(SCHEDULED_CELL["scheduled"][0], **change)
    cell = _scheduled_cell(scheduled=[entry])
    with pytest.raises(ValueError, match=match) as err:
        gen = make_generator(cell.config, cell.traffic)
        _tapes(gen, 100.0, 1, 40.0, warmup_s=1.0)
    assert entry["op"] in str(err.value)


@pytest.fixture
def _no_kernel_bucket_warmup(monkeypatch):
    """Compiling every kernel bucket in the Pallas interpreter would take
    minutes on the CPU; the window compiles what it needs."""
    import warmup
    monkeypatch.setattr(warmup, "warm_kernels", lambda *a, **k: None)


def _run_tiny_scheduled(capsys):
    cell = shrink(_scheduled_cell(files=SCHEDULED_CELL["parts"][0]
                                  ["files_per_dir"]))
    assert cell.config["namespace"]["parts"][-1]["files_per_dir"] == 2000
    assert cell.traffic["scheduled"][0]["at_s"] == pytest.approx(
        10.0 / float(load_benchmark()["run_seconds"]) * TINY_WINDOW_S)
    res = run(cell, 2 ** 31 + 515, TINY_WINDOW_S, False, PEAK,
              warmup_s=TINY_WARMUP_S)
    return res, capsys.readouterr().out


def test_tiny_cell_with_a_scheduled_delete_is_correct(
        _no_kernel_bucket_warmup, capsys):
    res, out = _run_tiny_scheduled(capsys)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 50
    line = [ln for ln in out.splitlines() if ln.startswith("scheduled ")]
    assert len(line) == 1 and line[0].startswith(
        "scheduled delete_subtree /big: due ")
    # 2,000 files in chunks of 1,000, then the directory's own row
    assert ", ok; waves 1; scanned 2000; peak_frontier 2001; chunks 3; " \
        "seconds " in line[0]


def test_subtree_delete_that_leaves_a_child_is_not_correct(
        _no_kernel_bucket_warmup, monkeypatch, capsys):
    from repro.core.subtree import SubtreeOps
    real = SubtreeOps._commit_chunk
    skipped = []

    def skip_one(self, chunk):
        if not skipped and len(chunk) == self.batch_size:
            skipped.append(chunk[-1])
            chunk = chunk[:-1]
        return real(self, chunk)
    monkeypatch.setattr(SubtreeOps, "_commit_chunk", skip_one)
    res, _ = _run_tiny_scheduled(capsys)
    assert skipped
    assert not res["correct"]
    assert res["checks"]["state_mismatches"]["value"] > 0
