"""Plain reference of the HopsFS metadata operations the traffic issues.

A path-keyed overlay on the configuration's :class:`NamespacePlan`: a
node is a tuple of the attributes a client can observe, a directory's
children are a set of names, and every operation is a few dictionary
updates written from the operation's documented semantics (paper §5 and
§6; the program's ``HopsFSOps``/``SubtreeOps`` docstrings). It imports
nothing of the program and takes nothing the program made: inode and
block ids, which the program allocates, are not part of any answer or
node here.

A :class:`RefFS` may be *forked*: a fork reads through to its parent and
writes only to itself, which is how the comparison evaluates a read
against the window's start state with a subset of the window's
mutations applied, without copying the namespace.

Answers are ``(error_name, value)`` pairs, ``error_name`` None on
success; :func:`normalize` maps the program's outcomes to the same form.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Set, Tuple

from nsplan import (DEFAULT_GROUP, DEFAULT_OWNER, DEFAULT_PERM, DEFAULT_REPL,
                    NamespacePlan)

Comps = Tuple[str, ...]

#: errors that are answers: the reference returns them too. Any other
#: error (lock timeouts, aborts, subtree-lock retries exhausted, shed
#: ops) means the system did not serve the op.
ANSWER_ERRORS = frozenset({"FileNotFound", "FileAlreadyExists", "FSError",
                           "LeaseConflict"})
READ_OPS = frozenset({"read", "stat", "ls", "content_summary", "du"})


class Node(NamedTuple):
    is_dir: bool
    perm: int
    owner: str
    group: str
    size: int
    repl: int
    under_construction: bool
    client: Optional[str]
    #: (size, replica datanodes) per block, in block-index order
    blocks: Tuple[Tuple[int, Tuple[int, ...]], ...]


INITIAL_DIR = Node(True, DEFAULT_PERM, DEFAULT_OWNER, DEFAULT_GROUP, 0,
                   DEFAULT_REPL, False, None, ())
INITIAL_FILE = INITIAL_DIR._replace(is_dir=False)


class FSFail(Exception):
    """An operation's error answer."""

    def __init__(self, name: str):
        super().__init__(name)
        self.name = name


#: public methods of :class:`RefFS` that are not operations
_NOT_OPS = frozenset({"apply", "fork", "get", "children", "has_op"})


def split(path: str) -> Comps:
    return tuple(c for c in path.split("/") if c)


class RefFS:
    """Namespace state: overlay of changes on the loaded plan."""

    def __init__(self, plan: NamespacePlan, parent: "Optional[RefFS]" = None):
        self.plan = plan
        self.parent = parent
        self.nodes: Dict[Comps, Optional[Node]] = {}
        self.kids: Dict[Comps, Set[str]] = {}
        if parent is None:
            self.n_inodes = plan.n_inodes
            self.n_blocks = 0
        else:
            self.n_inodes = parent.n_inodes
            self.n_blocks = parent.n_blocks

    def fork(self) -> "RefFS":
        return RefFS(self.plan, self)

    # -- storage -----------------------------------------------------------
    def get(self, comps: Comps) -> Optional[Node]:
        s: Optional[RefFS] = self
        while s is not None:
            if comps in s.nodes:
                return s.nodes[comps]
            s = s.parent
        kind = self.plan.lookup(comps)
        if kind is None:
            return None
        return INITIAL_DIR if kind else INITIAL_FILE

    def children(self, comps: Comps) -> Set[str]:
        s: Optional[RefFS] = self
        while s is not None:
            if comps in s.kids:
                return s.kids[comps]
            s = s.parent
        return set(self.plan.children(comps))

    def _kids(self, comps: Comps) -> Set[str]:
        got = self.kids.get(comps)
        if got is None:
            got = set(self.children(comps))
            self.kids[comps] = got
        return got

    def _add(self, comps: Comps, node: Node) -> None:
        self.nodes[comps] = node
        self._kids(comps[:-1]).add(comps[-1])
        self.n_inodes += 1

    def _remove(self, comps: Comps) -> None:
        node = self.get(comps)
        self.nodes[comps] = None
        self._kids(comps[:-1]).discard(comps[-1])
        self.n_inodes -= 1
        if node is not None:
            self.n_blocks -= len(node.blocks)

    # -- resolution --------------------------------------------------------
    def _target(self, comps: Comps) -> Optional[Node]:
        """The path's node; FileNotFound when an ancestor is missing."""
        for k in range(1, len(comps)):
            if self.get(comps[:k]) is None:
                raise FSFail("FileNotFound")
        return self.get(comps)

    def _existing(self, comps: Comps) -> Node:
        node = self._target(comps)
        if node is None:
            raise FSFail("FileNotFound")
        return node

    def _file(self, comps: Comps) -> Node:
        node = self._target(comps)
        if node is None or node.is_dir:
            raise FSFail("FileNotFound")
        return node

    @staticmethod
    def _check_lease(node: Node, client: str) -> None:
        # one live client per file; leases never expire in a run (the
        # election clock does not advance), so any other holder conflicts
        if node.under_construction and node.client not in (None, client):
            raise FSFail("LeaseConflict")

    # -- reads -------------------------------------------------------------
    def read(self, c: Comps, **_) -> Any:
        return self._existing(c).blocks

    def stat(self, c: Comps, **_) -> Any:
        n = self._existing(c)
        return (n.is_dir, n.perm, n.owner, n.group, n.size, n.repl)

    def ls(self, c: Comps, **_) -> Any:
        n = self._existing(c)
        return tuple(sorted(self.children(c))) if n.is_dir else ()

    def content_summary(self, c: Comps, **_) -> Any:
        n = self._existing(c)
        return (len(self.children(c)) if n.is_dir else 0, n.size)

    def du(self, c: Comps, **_) -> Any:
        n = self._existing(c)
        if not n.is_dir:
            return (1, 1, 0, n.size)
        inodes, files, dirs, size = 1, 0, 1, 0
        stack = [c]
        while stack:
            d = stack.pop()
            for name in self.children(d):
                k = d + (name,)
                kn = self.get(k)
                inodes += 1
                if kn.is_dir:
                    dirs += 1
                    stack.append(k)
                else:
                    files += 1
                    size += kn.size
        return (inodes, files, dirs, size)

    # -- mutations ---------------------------------------------------------
    def create(self, c: Comps, repl: int = 3, client: str = "client",
               overwrite: bool = False, **_) -> Any:
        target = self._target(c)
        if target is not None and not overwrite:
            raise FSFail("FileAlreadyExists")
        if not self.get(c[:-1]).is_dir:
            raise FSFail("FSError")
        node = INITIAL_FILE._replace(repl=repl, under_construction=True,
                                     client=client)
        if target is None:
            self._add(c, node)
        else:
            self.n_blocks -= len(target.blocks)
            self.nodes[c] = node
        return None

    def mkdirs(self, c: Comps, perm: int = DEFAULT_PERM, **_) -> Any:
        created = False
        for k in range(1, len(c) + 1):
            sub = c[:k]
            if self._target(sub) is not None:
                continue                    # mkdir's FileAlreadyExists
            if not self.get(sub[:-1]).is_dir:
                raise FSFail("FSError")
            self._add(sub, INITIAL_DIR._replace(perm=perm))
            created = True
        return created

    def add_block(self, c: Comps, client: str = "client", **_) -> Any:
        node = self._file(c)
        self._check_lease(node, client)
        self.nodes[c] = node._replace(blocks=node.blocks + ((0, ()),))
        self.n_blocks += 1
        return None

    def complete_block(self, c: Comps, block_id: int = -1, size: int = 0,
                       client: str = "client", **_) -> Any:
        node = self._file(c)
        self._check_lease(node, client)
        if block_id not in (None, -1) or not node.blocks:
            # the traffic completes "the last allocated block" only
            raise FSFail("FileNotFound")
        last = (size, tuple(range(3))[:node.repl])
        self.nodes[c] = node._replace(blocks=node.blocks[:-1] + (last,),
                                      size=node.size + size)
        return None

    def append(self, c: Comps, client: str = "client", **_) -> Any:
        node = self._file(c)
        self._check_lease(node, client)
        self.nodes[c] = node._replace(under_construction=True, client=client)
        return None

    def delete_file(self, c: Comps, **_) -> Any:
        node = self._existing(c)
        if node.is_dir:
            raise FSFail("FSError")
        self._remove(c)
        return None

    def rename_file(self, src: Comps, dst: Comps, **_) -> Any:
        snode = self._target(src)
        dnode = self._target(dst)
        if snode is None or snode.is_dir:
            raise FSFail("FileNotFound")
        if dnode is not None:
            raise FSFail("FileAlreadyExists")
        self._remove(src)
        self._add(dst, snode)
        self.n_blocks += len(snode.blocks)
        return None

    def _setattr(self, c: Comps, **changes) -> Any:
        node = self._existing(c)
        self.nodes[c] = node._replace(**changes)
        return None

    def chmod_file(self, c: Comps, perm: int = 0o640, **_) -> Any:
        return self._setattr(c, perm=perm)

    def chown_file(self, c: Comps, owner: str = "wluser", **_) -> Any:
        return self._setattr(c, owner=owner)

    def set_replication(self, c: Comps, repl: int = 2, **_) -> Any:
        return self._setattr(c, repl=repl)

    def _subtree_root(self, c: Comps) -> Node:
        node = self._existing(c)
        if not node.is_dir:
            raise FSFail("FSError")
        return node

    def chmod_subtree(self, c: Comps, perm: int = 0o640, **_) -> Any:
        # phase 3 updates the subtree root only (paper §6.2)
        self._subtree_root(c)
        return self._setattr(c, perm=perm)

    def chown_subtree(self, c: Comps, owner: str = "wluser", **_) -> Any:
        self._subtree_root(c)
        return self._setattr(c, owner=owner)

    def delete_subtree(self, c: Comps, **_) -> Any:
        self._subtree_root(c)
        order: List[Comps] = []
        stack = [c]
        while stack:
            d = stack.pop()
            order.append(d)
            if self.get(d).is_dir:
                stack.extend(d + (n,) for n in self.children(d))
        for p in reversed(order):
            self._remove(p)
        return (len(order), False)

    # -- dispatch ----------------------------------------------------------
    @classmethod
    def has_op(cls, op: str) -> bool:
        """Whether ``op`` names an operation of the reference (and not one
        of its storage, resolution or dispatch helpers)."""
        return (not op.startswith("_") and op not in _NOT_OPS
                and callable(getattr(cls, op, None)))

    def apply(self, op: str, path: str, path2: Optional[str],
              args: Dict[str, Any]) -> Tuple[Optional[str], Any]:
        if not self.has_op(op):
            raise NotImplementedError(f"the reference has no op {op!r}")
        fn = getattr(self, op)
        try:
            if op == "rename_file":
                dst = path2 if path2 is not None else path + ".mv"
                return None, fn(split(path), split(dst))
            return None, fn(split(path), **args)
        except FSFail as e:
            return e.name, None


def normalize(op: str, ok: bool, error: Optional[str],
              value: Any) -> Tuple[Optional[str], Any]:
    """A program outcome in the reference's answer form: ids, which the
    program allocates, are left out; list answers become tuples. A value
    of the wrong shape for its op is kept, marked, as an answer no
    reference gives."""
    if not ok:
        return error or "StoreError", None
    try:
        answer = _answer(op, value)
        hash(answer)
    except (TypeError, KeyError, IndexError, ValueError):
        return None, ("malformed", repr(value)[:200])
    return None, answer


def _answer(op: str, value: Any) -> Any:
    if op == "read":
        return tuple((b["size"], tuple(sorted(b["locations"])))
                     for b in value)
    if op == "stat":
        return (value["is_dir"], value["perm"], value["owner"],
                value["group"], value["size"], value["repl"])
    if op == "ls":
        return tuple(value)
    if op == "content_summary":
        return (value["children"], value["size"])
    if op == "du":
        return (value["inodes"], value["files"], value["dirs"],
                value["size"])
    if op == "delete_subtree":
        return (value["deleted"], value["crashed"])
    if op == "mkdirs":
        return value is not None
    return None
