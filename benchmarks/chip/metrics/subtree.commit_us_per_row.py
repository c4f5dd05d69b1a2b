"""subtree.commit_us_per_row: host microseconds the window's scheduled
``delete_subtree`` ops spent in phase-3 chunk commits
(``SubtreeOps.last_stats["commit_s"]``) per inode they deleted
(``deleted``), from their entries in ``ctx.subtree``. None where no such
op ran or its stats lack the counters."""


def read(ctx):
    paths = {path for _, op, path in ctx.scheduled if op == "delete_subtree"}
    ran = [s for s in ctx.subtree
           if s["op"] == "delete_subtree" and s["path"] in paths]
    if not ran or any("commit_s" not in s or "deleted" not in s
                      for s in ran):
        return None
    rows = sum(s["deleted"] for s in ran)
    return 1e6 * sum(s["commit_s"] for s in ran) / rows if rows else None
