"""planner.pinned_share: ops the batch planner kept in submission order
(``PlanReport.pinned_ops``) as a share, in %, of the ops it planned in
the window."""


def read(ctx):
    planned = ctx.counters.get("planned_ops", 0)
    if not planned:
        return None
    return 100.0 * ctx.counters["pinned_ops"] / planned
