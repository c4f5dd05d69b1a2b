"""Vectorized hint-chain resolution (HopsFS §5.1 inode hint cache) — XLA.

The client-side batch planner resolves every op's path against its hint
view: the client's own response-warmed cache first, the merged namenode
caches as fallback (``HintResolver`` / ``MultiCacheResolver``).  The
Python loop probes one ``(parent_id, name)`` per step, per op.  This
kernel walks ALL chains of a planner window at once: both cache views are
snapshotted into open-addressing hash tables (``repro.core.columnar.
HashIndex``) and the kernel advances every op's parent pointer one depth
per scan step — each step probing the client table, then the fallback
table, exactly the resolver's precedence.

Output encoding per (op, depth):

  child  > 0   resolved inode id        src 0 = client cache, 1 = fallback
  child == -1  miss (chain stops)       src -1
  child == -2  never probed (past the miss, or past the op's depth)
  child == -3  collided bucket — the host must re-resolve this op through
               the exact per-probe Python walk (names, not 32-bit hashes)

Like ``pkval``, whose lookup each depth step reuses, this is a plain XLA
program: the probes are random gathers into HBM-resident tables.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..pkval.kernel import MAX_PROBE, pkval


def hintchain(cp: jax.Array, cn: jax.Array, cv: jax.Array, fp: jax.Array,
              fn: jax.Array, fv: jax.Array, name_hashes: jax.Array,
              depths: jax.Array, *, root_id: int = 1,
              max_probe: int = MAX_PROBE):
    """client table [Cc] x fallback table [Cf] x chains [N, D] ->
    (child_ids [N, D] int32, src [N, D] int32)."""
    N, D = name_hashes.shape

    # rolled depth loop: compile time is independent of the chain-depth
    # bound; the scan walks depth-major rows of the transposed chains
    def _depth(carry, xs):
        parent, alive = carry
        d, nd = xs
        probing = alive & (d < depths)
        cval = pkval(cp, cn, cv, parent, nd, max_probe=max_probe)
        fval = pkval(fp, fn, fv, parent, nd, max_probe=max_probe)
        # resolver precedence: any client answer (including a collided
        # bucket — the Python walk might have resolved it) wins
        val = jnp.where(cval != jnp.int32(-1), cval, fval)
        found = probing & (val > 0)
        child_d = jnp.where(probing, val, jnp.int32(-2))
        src_d = jnp.where(found & (cval > 0), jnp.int32(0),
                          jnp.where(found, jnp.int32(1), jnp.int32(-1)))
        parent = jnp.where(found, val, parent)
        alive = alive & found
        return (parent, alive), (child_d, src_d)

    parent = jnp.full((N,), root_id, jnp.int32)
    alive = depths > 0
    _, (childs, srcs) = jax.lax.scan(
        _depth, (parent, alive),
        (jnp.arange(D, dtype=jnp.int32), name_hashes.T))
    return childs.T, srcs.T
