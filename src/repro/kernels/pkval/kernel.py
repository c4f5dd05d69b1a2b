"""Grouped-batch PK validation (HopsFS §5.1 batched PK reads) — XLA.

The columnar inode table (``repro.core.columnar``) maintains an
open-addressing hash index over its composite PK ``(parent_id,
name_hash32(name))``.  This kernel probes that index for a whole planner
window's ``(parent_id, name)`` chain in ONE launch: every probe walks the
same linear-probe sequence the host-side :class:`~repro.core.columnar.
HashIndex` inserts along (load factor <= 0.5, bounded probe length), so a
window of several hundred path components validates against the store in
one fused pass instead of per-row dict gets.

Sentinels share the host encoding: slot parent ``-1`` = empty (ends the
probe chain), ``-2`` = tombstone (probe continues), value ``-3`` = a
32-bit name-hash collision (two live names, one bucket) — collided keys
report "cannot validate" rather than a wrong id.  Probe rows with parent
``< 0`` are padding and always miss.

The lookup is a plain XLA program, not a Pallas kernel: each probe step
is a random gather into an index that lives in HBM and is far larger
than VMEM at real namespace sizes, and Mosaic lowers only 2-D gathers
from a VMEM block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..phash.kernel import GOLDEN, GOLDEN2

#: linear-probe bound shared with the host-side HashIndex insert path —
#: the host GROWS the table rather than place a key further than this,
#: so a kernel miss after MAX_PROBE steps is a real miss
MAX_PROBE = 8


def _bucket_hash(par, nam):
    """uint32 bucket mix over the composite key — one multiply per half,
    xor-folded, same avalanche finish as the scalar store hash."""
    h = ((par.astype(jnp.uint32) * jnp.uint32(GOLDEN))
         ^ (nam.astype(jnp.uint32) * jnp.uint32(GOLDEN2)))
    h = (h ^ (h >> jnp.uint32(16))).astype(jnp.uint32)
    return h


def pkval(tp: jax.Array, tn: jax.Array, tv: jax.Array, parents: jax.Array,
          name_hashes: jax.Array, *, max_probe: int = MAX_PROBE
          ) -> jax.Array:
    """index (tp/tn/tv [C]) x probes (parents/name_hashes [N]) ->
    resolved ids [N] int32 (-1 = no such row, -3 = hash-collided bucket).
    The hint-chain kernel runs this same lookup once per depth."""
    cap = tp.shape[0]
    slot = _bucket_hash(parents, name_hashes) & jnp.uint32(cap - 1)

    # rolled probe loop (NOT a static unroll): the XLA graph stays O(1)
    # in max_probe, keeping compile time flat
    def _step(step, carry):
        out, alive = carry
        j = ((slot + step.astype(jnp.uint32)) & jnp.uint32(cap - 1)) \
            .astype(jnp.int32)
        ep = jnp.take(tp, j)
        en = jnp.take(tn, j)
        ev = jnp.take(tv, j)
        hit = alive & (ep >= 0) & (ep == parents) & (en == name_hashes)
        out = jnp.where(hit, ev, out)
        alive = alive & ~hit & (ep != jnp.int32(-1))
        return out, alive

    out = jnp.full(parents.shape, -1, jnp.int32)
    alive = parents >= 0
    out, _ = jax.lax.fori_loop(0, max_probe, _step, (out, alive))
    return out
