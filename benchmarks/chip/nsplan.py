"""The namespace a configuration loads, as a pure function of its file.

One part kind, from the HopsFS paper (arXiv:1606.01588): ``trees``, the
§7.4 Spotify shape -- directories ``depth`` levels deep, ``dirs_per_dir``
subdirectories and ``files_per_dir`` files in every directory, names
``name_len`` characters long. ``trees`` copies sit side by side under the
root, which is how a namespace is widened to the configuration's inode
count.

The plan never materialises a million path strings: files are addressed
by index, and :meth:`NamespacePlan.lookup` / :meth:`children` answer for
any path by parsing it. The loader, the traffic samplers and the plain
reference all read the namespace from here, so they agree by
construction and none of them reads it from the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

#: attributes every bulk-loaded inode starts with (the program's
#: ``make_inode`` defaults: perm 0o755, owner/group "hops", repl 3)
DEFAULT_PERM = 0o755
DEFAULT_OWNER = "hops"
DEFAULT_GROUP = "hops"
DEFAULT_REPL = 3


def _pad(base: str, length: int, fill: str) -> str:
    return base + fill * max(0, length - len(base))


@dataclass(frozen=True)
class TreesPart:
    prefix: str
    trees: int
    depth: int
    dirs_per_dir: int
    files_per_dir: int
    name_len: int
    traffic: bool

    def tree_name(self, t: int) -> str:
        return self.prefix if self.trees == 1 else f"{self.prefix}{t:03d}"

    def dir_name(self, depth: int, k: int) -> str:
        return _pad(f"d{depth}x{k}", self.name_len, "u")

    def file_name(self, i: int) -> str:
        return _pad(f"f{i:04d}", self.name_len - len(".parquet"), "p") \
            + ".parquet"


class NamespacePlan:
    """Deterministic namespace of one configuration (see module doc)."""

    def __init__(self, parts: Sequence[dict]):
        self.trees: List[TreesPart] = []
        for p in parts:
            if p["kind"] == "trees":
                self.trees.append(TreesPart(
                    p["prefix"], p["trees"], p["depth"], p["dirs_per_dir"],
                    p["files_per_dir"], p["name_len"],
                    bool(p.get("traffic", False))))
            else:
                raise ValueError(f"unknown namespace part kind {p['kind']!r}")
        # relative directory paths of one tree in heap order: dir 0 is the
        # tree root, dir j has children k*... (one level per depth)
        self._rel: Dict[TreesPart, List[Tuple[str, ...]]] = {}
        self._rel_index: Dict[TreesPart, Dict[Tuple[str, ...], int]] = {}
        self._file_index: Dict[TreesPart, Dict[str, int]] = {}
        self._tree_of: Dict[str, Tuple[TreesPart, int]] = {}
        for part in self.trees:
            rel: List[Tuple[str, ...]] = [()]
            frontier = [()]
            for depth in range(1, part.depth):
                nxt = []
                for d in frontier:
                    for k in range(part.dirs_per_dir):
                        sub = d + (part.dir_name(depth, k),)
                        rel.append(sub)
                        nxt.append(sub)
                frontier = nxt
            self._rel[part] = rel
            self._rel_index[part] = {r: j for j, r in enumerate(rel)}
            self._file_index[part] = {part.file_name(i): i
                                      for i in range(part.files_per_dir)}
            for t in range(part.trees):
                self._tree_of[part.tree_name(t)] = (part, t)

    # -- sizes -----------------------------------------------------------
    def dirs_per_tree(self, part: TreesPart) -> int:
        return len(self._rel[part])

    @property
    def n_inodes(self) -> int:
        """Every inode, the root included."""
        n = 1
        for part in self.trees:
            per_tree = self.dirs_per_tree(part) * (1 + part.files_per_dir)
            n += part.trees * per_tree
        return n

    # -- enumeration (loader, samplers) ------------------------------------
    def tree_dirs(self, part: TreesPart, t: int) -> List[str]:
        """The directory paths of tree ``t``, parents before children."""
        root = "/" + part.tree_name(t)
        return [root + "".join("/" + c for c in rel)
                for rel in self._rel[part]]

    def traffic_dirs(self) -> List[str]:
        out: List[str] = []
        for part in self.trees:
            if part.traffic:
                for t in range(part.trees):
                    out.extend(self.tree_dirs(part, t))
        return out

    def traffic_file_count(self) -> int:
        n = 0
        for part in self.trees:
            if part.traffic:
                n += part.trees * self.dirs_per_tree(part) \
                    * part.files_per_dir
        return n

    def traffic_file(self, idx: int) -> str:
        """Path of traffic file ``idx`` (0 <= idx < traffic_file_count)."""
        for part in self.trees:
            if not part.traffic:
                continue
            per_tree = self.dirs_per_tree(part) * part.files_per_dir
            if idx < part.trees * per_tree:
                t, rem = divmod(idx, per_tree)
                j, i = divmod(rem, part.files_per_dir)
                rel = self._rel[part][j]
                return ("/" + part.tree_name(t) + "".join("/" + c for c in rel)
                        + "/" + part.file_name(i))
            idx -= part.trees * per_tree
        raise IndexError("traffic file index out of range")

    # -- point queries (reference) -----------------------------------------
    def part_of(self, comps: Sequence[str]) -> Optional[TreesPart]:
        """The part whose trees hold the path; None for the root and for
        names outside the loaded namespace."""
        hit = self._tree_of.get(comps[0]) if comps else None
        return hit[0] if hit is not None else None

    def lookup(self, comps: Sequence[str]) -> Optional[bool]:
        """None if the path is not in the loaded namespace, else is_dir."""
        if not comps:
            return True
        hit = self._tree_of.get(comps[0])
        if hit is None:
            return None
        part = hit[0]
        rest = tuple(comps[1:])
        if rest in self._rel_index[part]:
            return True
        if rest[:-1] in self._rel_index[part] \
                and rest[-1] in self._file_index[part]:
            return False
        return None

    def children(self, comps: Sequence[str]) -> List[str]:
        """Names of a loaded directory's loaded children."""
        if not comps:
            return [p.tree_name(t) for p in self.trees
                    for t in range(p.trees)]
        hit = self._tree_of.get(comps[0])
        if hit is None:
            return []
        part = hit[0]
        rest = tuple(comps[1:])
        if rest not in self._rel_index[part]:
            return []
        depth = len(rest) + 1
        subs = ([part.dir_name(depth, k) for k in range(part.dirs_per_dir)]
                if depth < part.depth else [])
        return subs + [part.file_name(i) for i in range(part.files_per_dir)]
