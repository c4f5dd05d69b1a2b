"""jit'd wrapper for the partition hash."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import mode
from .kernel import phash as _phash
from .kernel import phash_chain as _phash_chain


@functools.partial(jax.jit, static_argnames=("n_partitions",))
def phash(keys, n_partitions: int = 64):
    return _phash(keys, n_partitions=n_partitions,
                  interpret=mode.interpret())


@functools.partial(jax.jit, static_argnames=("n_partitions",))
def phash_chain(parents, names, hints, depths, n_partitions: int = 64):
    return _phash_chain(parents, names, hints, depths,
                        n_partitions=n_partitions,
                        interpret=mode.interpret())


def phash_partitions(keys, n_partitions: int = 64) -> np.ndarray:
    """Partition ids for a whole batch of integer keys at once.

    This is the vectorized path->partition step of the batched request
    pipeline: a namenode hashes every hinted inode id in a pulled batch in
    one kernel launch instead of per-op Python hashing. Results match
    ``repro.core.store._hash_key(key) % n_partitions`` exactly for integer
    keys (both sides operate on the low 32 bits).

    Keys are padded to a power-of-two length (>= 8) so the 1-D grid always
    tiles evenly and jit recompiles are bounded to O(log N) shapes.
    """
    arr = np.asarray(keys, dtype=np.int64) & 0xFFFFFFFF
    n = arr.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    padded = 8
    while padded < n:
        padded *= 2
    buf = np.zeros(padded, dtype=np.uint32)
    buf[:n] = arr.astype(np.uint32)
    out = phash(jnp.asarray(buf), n_partitions=n_partitions)
    return np.asarray(out)[:n]


def _pad_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def phash_chains(parent_ids, name_hashes, hint_ids, depths,
                 n_partitions: int = 64
                 ) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    """Fused chain hashing for the client-side batch planner: ONE kernel
    launch over every path's (parent_id, name) component chain returns

      * ``comp_parts [N, D]`` — partition of every component's inode row
        (inodes are partitioned by parent_id, §4.2), matching
        ``repro.core.store._hash_key(parent_id) % n_partitions`` exactly;
      * ``hint_parts [N]``    — partition of each op's hinted (leaf) inode
        id, the key the planner groups partition-aligned batches on;
      * ``sigs [N]``          — 32-bit fold of the whole chain, a
        constant-time path-equality probe for chain-level consumers.

    ``parent_ids``/``name_hashes`` are [N, D] arrays padded with zeros
    beyond ``depths[n]`` components. N is padded to a power of two (>= 8)
    so the 1-D grid tiles evenly and jit recompiles stay O(log N); the
    kernel takes the chains depth-major, so they are transposed here."""
    par = np.asarray(parent_ids, dtype=np.int64) & 0xFFFFFFFF
    nam = np.asarray(name_hashes, dtype=np.int64) & 0xFFFFFFFF
    hin = np.asarray(hint_ids, dtype=np.int64) & 0xFFFFFFFF
    dep = np.asarray(depths, dtype=np.int32)
    n = par.shape[0]
    if n == 0:
        d0 = par.shape[1] if par.ndim == 2 else 0
        return (np.zeros((0, d0), np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.uint32))
    d = max(1, par.shape[1])
    pn = _pad_pow2(n)
    pbuf = np.zeros((d, pn), np.uint32)
    nbuf = np.zeros((d, pn), np.uint32)
    pbuf[:par.shape[1], :n] = par.T.astype(np.uint32)
    nbuf[:nam.shape[1], :n] = nam.T.astype(np.uint32)
    hbuf = np.zeros((1, pn), np.uint32)
    hbuf[0, :n] = hin.astype(np.uint32)
    dbuf = np.zeros((1, pn), np.int32)
    dbuf[0, :n] = dep
    comp, hint_parts, sigs = phash_chain(
        jnp.asarray(pbuf), jnp.asarray(nbuf), jnp.asarray(hbuf),
        jnp.asarray(dbuf), n_partitions=n_partitions)
    return (np.asarray(comp)[:, :n].T, np.asarray(hint_parts)[0, :n],
            np.asarray(sigs)[0, :n])
