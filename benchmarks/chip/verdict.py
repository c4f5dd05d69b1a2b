"""The comparison that decides ``correct``: what the timed path answered,
and the store it left, against the plain reference (:mod:`refmodel`).

Each call of the open-loop driver is one planner window, and windows are
barriers: the program plans, executes and absorbs one window before the
next. Inside a window it reorders freely, so the comparison asks for
what serializability guarantees and no more:

* a mutation's answer is the one the reference gives when the window's
  mutations run in submission order (the planner keeps conflicting
  mutations in that order and runs the others where they commute);
* a read's answer is one the reference gives at the window's start
  state with *some* subset of the window's mutations that touch the
  read's path applied in submission order. A read that no mutation of
  its window touches therefore has exactly one right answer;
* after the run, every path any dispatched op named (with its
  ancestors), and a seeded sample of the rest of the namespace, reads
  back as the reference holds it; directories named get their listing
  compared; the store's inode and block counts equal the reference's.
  Where the traffic schedules subtree ops, each scheduled directory and
  a seeded sample of its loaded children are compared too.

Ops the system did not serve (errors outside ``ANSWER_ERRORS``) are left
out of the reference: a refused op must leave no trace, which the state
comparison checks.
"""
from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from nsplan import NamespacePlan
from refmodel import (ANSWER_ERRORS, READ_OPS, Comps, Node, RefFS, split)

#: reads touched by more mutations of their window than this are counted
#: as not compared (2^m subset states would be evaluated)
MAX_SUBSET_MUTATIONS = 12
#: loaded children of each scheduled directory compared after the run
SCHEDULED_CHILDREN = 200
#: deep-listing ops: a mutation anywhere below the path changes the answer
_DEEP = frozenset({"du"})
#: listing ops: a mutation of a direct child changes the answer
_LISTING = frozenset({"ls", "content_summary"})
#: answers made of counts and sums
_ADDITIVE = frozenset({"du", "content_summary"})


def op_paths(wop: Any) -> List[Comps]:
    out = [split(wop.path)]
    if wop.op == "rename_file":
        out.append(split(wop.path2 if wop.path2 is not None
                         else wop.path + ".mv"))
    return out


class MutationIndex:
    """The mutations of one window, indexed by the paths they name, to
    find those that can change a read's answer without a scan."""

    def __init__(self, muts: Sequence[Any]):
        self.exact: Dict[Comps, List[int]] = {}
        self.below: Dict[Comps, List[Tuple[int, int]]] = {}
        for k, m in enumerate(muts):
            for q in op_paths(m):
                self.exact.setdefault(q, []).append(k)
                for d in range(len(q)):
                    self.below.setdefault(q[:d], []).append((k, len(q) - d))

    def touching(self, read: Any) -> Tuple[int, ...]:
        """Mutations that can change ``read``'s answer: those naming its
        path or an ancestor; for a listing also a direct child, for
        ``du`` anything below."""
        r = split(read.path)
        rel: Set[int] = set()
        for d in range(len(r) + 1):
            rel.update(self.exact.get(r[:d], ()))
        if read.op in _DEEP:
            rel.update(k for k, _ in self.below.get(r, ()))
        elif read.op in _LISTING:
            rel.update(k for k, extra in self.below.get(r, ()) if extra == 1)
        return tuple(sorted(rel))


@dataclass
class Verdict:
    op_mismatches: int = 0
    du_mismatches: int = 0
    state_mismatches: int = 0
    compared_ops: int = 0
    uncompared_reads: int = 0
    unserved: int = 0
    examples: List[str] = field(default_factory=list)

    def note(self, text: str) -> None:
        if len(self.examples) < 8:
            self.examples.append(text if len(text) <= 400
                                 else text[:400] + "...")

    def numbers(self) -> Dict[str, Tuple[int, int]]:
        """Each compared number with its limit (exact comparisons)."""
        return {"op_mismatches": (self.op_mismatches, 0),
                "du_mismatches": (self.du_mismatches, 0),
                "state_mismatches": (self.state_mismatches, 0)}


def _served(answer: Tuple[Optional[str], Any]) -> bool:
    return answer[0] is None or answer[0] in ANSWER_ERRORS


def _ask(f: RefFS, w: Any) -> Tuple[Optional[str], Any]:
    return f.apply(w.op, w.path, w.path2, dict(w.args))


def _additive_answers(ref: RefFS, read: Any, muts: Sequence[Any]
                      ) -> Optional[Set[Any]]:
    """``du`` and ``content_summary`` answers are counts and sums. Where
    each mutation moves them by a fixed amount, and all of them together
    by the sum of those amounts, the answers of every subset are the
    start answer plus a subset sum: found without 2^m evaluations. None
    where the mutations interact (the caller then enumerates)."""
    base = _ask(ref, read)
    if base[0] is not None:
        return None
    deltas = []
    for m in muts:
        f = ref.fork()
        _ask(f, m)
        a = _ask(f, read)
        if a[0] is not None:
            return None
        deltas.append(tuple(x - y for x, y in zip(a[1], base[1])))
    f = ref.fork()
    for m in muts:
        _ask(f, m)
    full = _ask(f, read)
    if full[0] is not None or list(full[1]) != [
            b + sum(d[k] for d in deltas) for k, b in enumerate(base[1])]:
        return None
    sums = {tuple(base[1])}
    for d in deltas:
        if any(d):
            sums |= {tuple(x + y for x, y in zip(s, d)) for s in sums}
    return {(None, s) for s in sums}


def _read_answers(ref: RefFS, read: Any, muts: Sequence[Any]
                  ) -> Optional[Set[Any]]:
    if not muts:
        return {_ask(ref, read)}
    if read.op in _ADDITIVE:
        got = _additive_answers(ref, read, muts)
        if got is not None:
            return got
    if len(muts) > MAX_SUBSET_MUTATIONS:
        return None
    out: Set[Any] = set()
    for mask in range(1 << len(muts)):
        f = ref.fork()
        for k, m in enumerate(muts):
            if mask >> k & 1:
                _ask(f, m)
        out.add(_ask(f, read))
    return out


def check_windows(ref: RefFS, windows: Sequence[Sequence[Tuple[Any, Any]]],
                  v: Verdict, touched: Set[Comps]) -> None:
    """Compare every answer of every window, advancing ``ref``."""
    for window in windows:
        served = []
        for wop, ans in window:
            for p in op_paths(wop):
                touched.add(p)
            if _served(ans):
                served.append((wop, ans))
            else:
                v.unserved += 1
        muts = [w for w, _ in served if w.op not in READ_OPS]
        index = MutationIndex(muts)
        memo: Dict[Tuple, Optional[Set[Any]]] = {}
        for wop, ans in served:
            if wop.op not in READ_OPS:
                continue
            rel = index.touching(wop)
            key = (wop.op, wop.path, rel)
            if key not in memo:
                memo[key] = _read_answers(ref, wop, [muts[k] for k in rel])
            valid = memo[key]
            if valid is None:
                v.uncompared_reads += 1
                continue
            v.compared_ops += 1
            if ans not in valid:
                v.op_mismatches += 1
                if wop.op in ("du", "content_summary"):
                    v.du_mismatches += 1
                v.note(f"{wop.op} {wop.path}: answered {ans!r}, "
                       f"reference {sorted(valid, key=repr)[:2]!r}")
        for wop, ans in served:
            if wop.op in READ_OPS:
                continue
            exp = ref.apply(wop.op, wop.path, wop.path2, dict(wop.args))
            v.compared_ops += 1
            if ans != exp:
                v.op_mismatches += 1
                v.note(f"{wop.op} {wop.path}: answered {ans!r}, "
                       f"reference {exp!r}")


class RefView:
    """A :class:`RefFS` read through the state-view interface."""

    def __init__(self, ref: RefFS):
        self.ref = ref

    def node(self, comps: Comps) -> Optional[Node]:
        return self.ref.get(comps)

    def children(self, comps: Comps) -> Set[str]:
        return set(self.ref.children(comps))

    def n_inodes(self) -> int:
        return self.ref.n_inodes

    def n_blocks(self) -> int:
        return self.ref.n_blocks


class StoreView:
    """The program's store after the window, read through its tables."""

    def __init__(self, store: Any):
        self.inode = store.table("inode")
        self.block = store.table("block")
        self.replica = store.table("replica")

    def _row(self, comps: Comps) -> Optional[Dict[str, Any]]:
        row = self.inode.get((0, ""))
        for name in comps:
            if row is None:
                return None
            row = self.inode.get((row["id"], name))
        return row

    def node(self, comps: Comps) -> Optional[Node]:
        row = self._row(comps)
        if row is None:
            return None
        reps: Dict[int, List[int]] = {}
        for r in self.replica.scan_index("inode_id", row["id"]):
            reps.setdefault(r["block_id"], []).append(r["datanode_id"])
        blocks = tuple((b["size"], tuple(sorted(reps.get(b["block_id"], ()))))
                       for b in sorted(self.block.scan_index(
                           "inode_id", row["id"]), key=lambda b: b["index"]))
        return Node(bool(row["is_dir"]), row["perm"], row["owner"],
                    row["group"], row["size"], row["repl"],
                    bool(row.get("under_construction")), row.get("client"),
                    blocks)

    def children(self, comps: Comps) -> Set[str]:
        row = self._row(comps)
        if row is None:
            return set()
        return {r["name"] for r in self.inode.scan_index("parent_id",
                                                         row["id"])}

    def n_inodes(self) -> int:
        return self.inode.n_rows

    def n_blocks(self) -> int:
        return self.block.n_rows


def sample_paths(plan: NamespacePlan, seed: int, n: int) -> Set[Comps]:
    rng = random.Random(f"{seed}/state-sample")
    n_files = plan.traffic_file_count()
    dirs = plan.traffic_dirs()
    out: Set[Comps] = set()
    for _ in range(n):
        if rng.random() < 0.2:
            out.add(split(rng.choice(dirs)))
        else:
            out.add(split(plan.traffic_file(rng.randrange(n_files))))
    return out


def scheduled_sample(plan: NamespacePlan, dirs: Sequence[str], seed: int,
                     n: int) -> Set[Comps]:
    """Each scheduled directory and ``n`` of its loaded children, drawn
    from ``seed``: rows a subtree op left behind or took wrongly show
    here, beyond the inode count."""
    rng = random.Random(f"{seed}/scheduled-sample")
    out: Set[Comps] = set()
    for d in dirs:
        c = split(d)
        kids = plan.children(c)
        out.add(c)
        out.update(c + (k,) for k in rng.sample(kids, min(n, len(kids))))
    return out


def check_state(ref: RefFS, view: Any, touched: Set[Comps],
                sample: Set[Comps], v: Verdict,
                max_listing: int = 100_000) -> None:
    if view.n_inodes() != ref.n_inodes:
        v.state_mismatches += 1
        v.note(f"inode count {view.n_inodes()}, reference {ref.n_inodes}")
    if view.n_blocks() != ref.n_blocks:
        v.state_mismatches += 1
        v.note(f"block count {view.n_blocks()}, reference {ref.n_blocks}")
    paths: Set[Comps] = {()}
    for c in touched:
        for k in range(1, len(c) + 1):
            paths.add(c[:k])
    listed = set(paths)
    paths |= sample
    for c in sorted(paths):
        exp = ref.get(c)
        got = view.node(c)
        if got != exp:
            v.state_mismatches += 1
            v.note(f"/{'/'.join(c)}: store {got!r}, reference {exp!r}")
            continue
        if exp is not None and exp.is_dir and c in listed:
            want = ref.children(c)
            if len(want) <= max_listing and view.children(c) != set(want):
                v.state_mismatches += 1
                v.note(f"/{'/'.join(c)}: listing differs from the "
                       f"reference")


def judge(plan: NamespacePlan, windows: Sequence[Sequence[Tuple[Any, Any]]],
          view: Any, seed: int, *, sample: int = 2000,
          scheduled: Sequence[str] = ()) -> Verdict:
    """Run the whole comparison: answers window by window, then state.
    ``scheduled`` names the directories of the traffic's scheduled ops."""
    v = Verdict()
    ref = RefFS(plan)
    touched: Set[Comps] = set()
    check_windows(ref, windows, v, touched)
    paths = sample_paths(plan, seed, sample)
    paths |= scheduled_sample(plan, scheduled, seed, SCHEDULED_CHILDREN)
    check_state(ref, view, touched, paths, v)
    return v
