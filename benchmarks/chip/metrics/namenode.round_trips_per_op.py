"""namenode.round_trips_per_op: database round trips the namenodes
committed in the window (sum of ``agg_cost.round_trips`` deltas) per op
served."""


def read(ctx):
    served = sum(ctx.served)
    if not served or "round_trips" not in ctx.counters:
        return None
    return ctx.counters["round_trips"] / served
