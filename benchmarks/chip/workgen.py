"""The one traffic generator: a mix table, a popularity law and an arrival
process, all read from a cell's traffic file, drawn from a seed.

The samplers and op builders are the benchmark's own copies of the
program's Spotify workload generator, so that no later change to the
program can change the traffic it is measured on:

* the op builders copy ``MIX_BINDINGS`` (src/repro/core/ops_registry.py,
  lines 476-575), argument pools included;
* the liveness rule (a deleted or renamed target is not sampled again)
  copies ``SpotifyWorkload._is_dead``/``live_file``/``live_dir``/
  ``retire``/``next_create_path`` (src/repro/core/workload.py, lines
  213-249);
* the popularity law copies ``SyntheticNamespace._pop_weights``/
  ``sample_file`` (src/repro/core/workload.py, lines 157-166): rank ``r``
  has weight ``1/(r+1)**s``. The copy draws from precomputed cumulative
  weights with ``bisect`` -- the same draw ``random.choices(weights=)``
  makes, without its O(n) pass per draw over 10^6 files.

Only the ``WorkloadOp`` record type comes from the program.

A traffic file may also carry ``scheduled``: fixed operations on named
directories of ``traffic: false`` namespace parts, each due at a fixed
offset of the warm-up or the window (:class:`Scheduled`). They are put
into the tape after it is drawn and dealt, so the tape is the same with
or without them, and the seed's dealing never moves them.
"""
from __future__ import annotations

import bisect
import itertools
import random
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from nsplan import NamespacePlan
from refmodel import RefFS, split

#: argument pools (ops_registry.py lines 476-480)
PERM_POOL = (0o644, 0o640, 0o755, 0o750, 0o700)
OWNER_POOL = tuple(f"user{i}" for i in range(8))
REPL_POOL = (1, 2, 3)
BLOCK_SIZE_POOL = (1 << 26, 1 << 25, 1 << 24, 1 << 20)


def workload_op(*args, **kw):
    """The program's operation record (imported late: the generator's
    tests and the plain reference need no program import otherwise)."""
    from repro.core.ops_registry import WorkloadOp
    return WorkloadOp(*args, **kw)


class ZipfSampler:
    """Rank sampler for weights ``1/(r+1)**s``, r in [0, n). Draws
    ``bisect(cum, rng.random() * total)``: draw for draw what
    ``rng.choices(range(n), weights=w)`` returns for the same state."""

    def __init__(self, n: int, s: float):
        self.cum = list(itertools.accumulate(1.0 / (r + 1) ** s
                                             for r in range(n)))
        self.total = self.cum[-1]
        self.n = n

    def draw(self, rng: random.Random) -> int:
        return bisect.bisect(self.cum, rng.random() * self.total, 0,
                             self.n - 1)


#: the parts of a run a scheduled op can fall due in
PHASES = ("warmup", "window")


@dataclass(frozen=True)
class Scheduled:
    """One entry of a traffic file's ``scheduled`` list: ``op`` on the
    directory ``path``, due ``at_s`` seconds into ``phase``, with ``args``
    named as the mix's ops name theirs (``perm``, ``owner``, ...)."""
    op: str
    path: str
    at_s: float
    phase: str
    args: Dict[str, Any] = field(default_factory=dict)


def parse_scheduled(entry: dict, plan: NamespacePlan) -> Scheduled:
    """``entry`` as a :class:`Scheduled`; ValueError, naming the entry,
    unless it names an op of the plain reference on a directory of a
    ``traffic: false`` part (one Table 1 never samples), in a known
    phase."""
    try:
        s = Scheduled(**entry)
    except TypeError as e:
        raise ValueError(f"scheduled entry {entry!r}: {e}") from None
    comps = split(s.path)
    part = plan.part_of(comps)
    if plan.lookup(comps) is not True:
        why = "its path is not a directory of the namespace"
    elif part is None or part.traffic:
        why = "its path is not in a traffic: false namespace part"
    elif not RefFS.has_op(s.op):
        why = f"the plain reference has no op {s.op!r}"
    elif s.phase not in PHASES:
        why = f"its phase is not one of {PHASES}"
    else:
        return s
    raise ValueError(f"scheduled entry {entry!r}: {why}")


class TrafficGenerator:
    """Stream of operations over a configuration's traffic namespace,
    with a traffic file's scheduled operations beside it."""

    def __init__(self, plan: NamespacePlan, mix: Sequence[Sequence],
                 zipf_s: float, popularity_seed: int,
                 scheduled: Sequence[dict] = ()):
        self.plan = plan
        self.scheduled = [parse_scheduled(e, plan) for e in scheduled]
        #: ``(index, entry)`` of each scheduled op the last
        #: :meth:`schedule` call put into its tape
        self.placed: List[Tuple[int, Scheduled]] = []
        self.ops = [m[0] for m in mix]
        self.cum = list(itertools.accumulate(float(m[1]) for m in mix))
        self.dir_frac = {m[0]: float(m[2]) for m in mix}
        self.dirs = plan.traffic_dirs()
        n_files = plan.traffic_file_count()
        self.zipf = ZipfSampler(n_files, zipf_s)
        # popularity ranks are dealt to files by a fixed permutation, so
        # the hot files spread over the whole namespace
        perm = list(range(n_files))
        random.Random(popularity_seed).shuffle(perm)
        self.rank_to_file = perm
        self.rng = random.Random(0)
        self._create_seq = 0
        self._dead: Set[str] = set()
        self._dead_dirs: Set[str] = set()

    # -- liveness (workload.py lines 213-249) ------------------------------
    def _is_dead(self, path: str) -> bool:
        if path in self._dead:
            return True
        prefix = ""
        for seg in path.split("/"):
            if not seg:
                continue
            prefix += "/" + seg
            if prefix in self._dead_dirs:
                return True
        return False

    def sample_file(self) -> str:
        return self.plan.traffic_file(
            self.rank_to_file[self.zipf.draw(self.rng)])

    def sample_dir(self) -> str:
        return self.rng.choice(self.dirs)

    def live_file(self) -> str:
        for _ in range(32):
            f = self.sample_file()
            if not self._is_dead(f):
                return f
        return self.sample_file()

    def live_dir(self) -> str:
        for _ in range(32):
            d = self.sample_dir()
            if not self._is_dead(d):
                return d
        return self.sample_dir()

    def retire(self, path: str, *, is_dir: bool) -> None:
        (self._dead_dirs if is_dir else self._dead).add(path)

    def next_create_path(self) -> str:
        self._create_seq += 1
        return f"{self.live_dir()}/w{self._create_seq:08d}"

    # -- op builders (ops_registry.py lines 485-575) -----------------------
    def build(self, name: str, on_dir: bool):
        rng = self.rng
        if name == "mkdirs":
            d = self.live_dir()
            return workload_op("mkdirs", f"{d}/new{rng.randrange(1 << 30):x}",
                               on_dir=True)
        if name == "create":
            return workload_op("create", self.next_create_path(),
                               args={"repl": rng.choice(REPL_POOL)})
        if name == "rename":
            src = self.live_file()
            self.retire(src, is_dir=False)
            return workload_op("rename_file", src, src + ".mv",
                               on_dir=on_dir)
        if name == "delete":
            if on_dir:
                d = self.live_dir()
                self.retire(d, is_dir=True)
                return workload_op("delete_subtree", d, on_dir=True)
            f = self.live_file()
            self.retire(f, is_dir=False)
            return workload_op("delete_file", f)
        if name == "set_permissions":
            p = self.live_dir() if on_dir else self.live_file()
            return workload_op("chmod_subtree" if on_dir else "chmod_file",
                               p, on_dir=on_dir,
                               args={"perm": rng.choice(PERM_POOL)})
        if name == "set_owner":
            p = self.live_dir() if on_dir else self.live_file()
            return workload_op("chown_subtree" if on_dir else "chown_file",
                               p, on_dir=on_dir,
                               args={"owner": rng.choice(OWNER_POOL)})
        if name == "set_replication":
            return workload_op("set_replication", self.live_file(),
                               args={"repl": rng.choice(REPL_POOL)})
        if name == "complete":
            return workload_op("complete_block", self.live_file(),
                               args={"block_id": -1,
                                     "size": rng.choice(BLOCK_SIZE_POOL)})
        if name in ("add_block", "read", "append"):
            return workload_op(name, self.live_file())
        if name in ("ls", "stat", "content_summary", "du"):
            p = self.live_dir() if on_dir else self.live_file()
            return workload_op(name, p, on_dir=on_dir)
        raise ValueError(f"mix entry {name!r} has no builder")

    def next_op(self):
        name = self.ops[bisect.bisect(self.cum, self.rng.random()
                                      * self.cum[-1], 0, len(self.ops) - 1)]
        on_dir = self.rng.random() < self.dir_frac[name]
        return self.build(name, on_dir)

    def schedule(self, seed: str, rate: float, seconds: float, *,
                 work_seed: Optional[str] = None, block_s: float = 2.0,
                 phase: Optional[str] = None
                 ) -> Tuple[List[float], list]:
        """Poisson arrivals at ``rate`` over ``[0, seconds)`` and one op
        per arrival. Without ``work_seed`` both are drawn from ``seed``
        (arrival gaps and op content from separate streams). With it, the
        gaps and the ops are drawn once from ``work_seed`` as one tape,
        cut into blocks of ``block_s`` seconds, and ``seed`` only deals
        the blocks in another order: every seed then offers the same work
        (the same ops, the same gaps, the same total time) and runs
        differ only in the order of its blocks.

        Then each scheduled op of ``phase`` (none without one) goes in
        at its ``at_s``, after every tape op due at or before it; their
        positions are left in :attr:`placed`."""
        arrivals = random.Random(f"{work_seed or seed}/arrivals")
        self.rng = random.Random(f"{work_seed or seed}/ops")
        gaps: List[float] = []
        prev, t = 0.0, arrivals.expovariate(rate)
        while t < seconds:
            gaps.append(t - prev)
            prev, t = t, t + arrivals.expovariate(rate)
        ops = [self.next_op() for _ in gaps]
        if work_seed is not None:
            # deal whole blocks of ``block_s`` seconds of the tape in the
            # seed's order: bursts and quiet spells stay as drawn
            starts = [0]
            t = 0.0
            for i, g in enumerate(gaps):
                t += g
                if t >= block_s * len(starts) and i + 1 < len(gaps):
                    starts.append(i + 1)
            blocks = [list(range(a, b)) for a, b in
                      zip(starts, starts[1:] + [len(gaps)])]
            random.Random(f"{seed}/order").shuffle(blocks)
            idx = [i for b in blocks for i in b]
            gaps = [gaps[i] for i in idx]
            ops = [ops[i] for i in idx]
        due = list(itertools.accumulate(gaps))
        self.placed = []
        for s in sorted((s for s in self.scheduled if s.phase == phase),
                        key=lambda s: s.at_s):
            if not 0.0 <= s.at_s < seconds:
                raise ValueError(f"scheduled {s.op} {s.path}: at_s {s.at_s} "
                                 f"is outside the {phase} [0, {seconds})")
            k = bisect.bisect_right(due, s.at_s)
            due.insert(k, s.at_s)
            ops.insert(k, workload_op(s.op, s.path, on_dir=True,
                                      args=dict(s.args)))
            self.placed.append((k, s))
        return due, ops


def make_generator(config: dict, traffic: dict,
                   plan: Optional[NamespacePlan] = None) -> TrafficGenerator:
    plan = plan or NamespacePlan(config["namespace"]["parts"])
    pop = config["popularity"]
    return TrafficGenerator(plan, traffic["mix"], pop["zipf_s"], pop["seed"],
                            traffic.get("scheduled", ()))
