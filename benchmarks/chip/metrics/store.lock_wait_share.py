"""store.lock_wait_share: row-lock acquires that found a conflicting
holder and waited (``LockManager.wait_count``) as a share, in %, of all
locking acquires (``acquire_count``) in the window."""


def read(ctx):
    acquires = ctx.counters.get("lock_acquires", 0)
    if not acquires:
        return None
    return 100.0 * ctx.counters["lock_waits"] / acquires
