"""Subtree wave expansion + segment aggregation (HopsFS §6 phase 2) — XLA.

The incremental subtree protocol (``repro.core.subtree``) walks a
directory tree as BFS *waves*: a wave is the set of directory inode ids
whose children must be resolved next.  On the columnar store the child
relation is already materialized as struct-of-arrays hot columns
(``id`` / ``parent_id`` / ``is_dir`` / ``size``), so one wave resolves in
ONE fused launch instead of a partition-pruned scan per directory:

* **expansion** — for every table slot, a lower-bound binary search of its
  ``parent_id`` against the sorted wave gives ``seg``: the wave member the
  slot is a child of (``-1`` = not a child of this wave, including cleared
  slots whose parent is the ``-1`` sentinel);
* **aggregation** — segment sums fold per-child ``1`` / ``is_dir`` /
  ``size`` into per-wave-member ``counts`` / ``dirs`` / ``sizes`` (the
  sums behind ``du`` and ``content_summary``).

The wave is padded with ``INT32_MAX`` (never a real inode id, keeps the
array sorted); slot-side padding uses parent ``-1`` which can never match
a wave member (wave ids are ``>= 0``).  Everything is int32 — the suite
runs with x64 disabled — so sizes are aggregated as int32 partial sums
(wrapping, like the numpy oracle) and widened host-side.

This is a plain XLA program: the search gathers from the wave at
data-dependent positions and the sums scatter into it, neither of which
Mosaic lowers from a VMEM block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def treeagg(wave: jax.Array, par: jax.Array, isdir: jax.Array,
            size: jax.Array):
    """wave [W] (sorted, INT32_MAX-padded) x slots (par/isdir/size [C]) ->
    (seg [C], counts [W], dirs [W], sizes [W]) int32."""
    (W,) = wave.shape
    lo = jnp.searchsorted(wave, par, side="left").astype(jnp.int32)
    found = ((par >= 0) & (lo < W)
             & (jnp.take(wave, jnp.minimum(lo, W - 1)) == par))
    seg = jnp.where(found, lo, jnp.int32(-1))
    # misses get segment W, which segment_sum drops
    ids = jnp.where(found, lo, jnp.int32(W))

    def _sum(v):
        return jax.ops.segment_sum(v.astype(jnp.int32), ids, num_segments=W)

    return (seg, _sum(jnp.ones_like(par)), _sum(isdir), _sum(size))
