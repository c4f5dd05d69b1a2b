"""pkval_roofline: least time of the pkval launches in the window
(24 B per probe over HBM bandwidth, workcount.py) as a share, in %, of
the summed device time of their ``jit_pkval`` events. Probes are the
planner's and the namenodes' ``pkval_probes``: counted only for launches
that ran (below the gate nothing is counted; on the chip a failed launch
raises)."""
from workcount import pkval_bytes, roofline_percent


def read(ctx):
    if ctx.trace is None:
        return None
    probes = (ctx.counters.get("planner_pkval_probes", 0)
              + ctx.counters.get("nn_pkval_probes", 0))
    return roofline_percent(pkval_bytes(probes),
                            ctx.trace["kernel_s"].get("pkval"), ctx.peak)
