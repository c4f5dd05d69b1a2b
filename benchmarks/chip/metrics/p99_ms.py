"""p99_ms: 99th percentile, in ms, of the latency of every op served in
the window, each from its due time to the return of the call that
served it."""
from openloop import percentile


def read(ctx):
    lat = ctx.window.latencies_s(ctx.served)
    return 1000.0 * percentile(lat, 99) if lat else None
