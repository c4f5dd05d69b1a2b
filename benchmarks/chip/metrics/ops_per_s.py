"""ops_per_s: ops the system served in the window over the window's
length (from its opening to the return of its last call)."""


def read(ctx):
    return sum(ctx.served) / ctx.window.seconds
