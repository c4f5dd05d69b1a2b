"""subtree.scan_us_per_row: host microseconds the window's scheduled
``delete_subtree`` ops spent in phase-2 page scans
(``SubtreeOps.last_stats["scan_s"]``) per child row they scanned
(``scanned``), from their entries in ``ctx.subtree``. None where no such
op ran or its stats lack the counters."""


def read(ctx):
    paths = {path for _, op, path in ctx.scheduled if op == "delete_subtree"}
    ran = [s for s in ctx.subtree
           if s["op"] == "delete_subtree" and s["path"] in paths]
    if not ran or any("scan_s" not in s for s in ran):
        return None
    rows = sum(s["scanned"] for s in ran)
    return 1e6 * sum(s["scan_s"] for s in ran) / rows if rows else None
