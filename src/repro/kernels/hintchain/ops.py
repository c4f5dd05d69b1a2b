"""jit'd wrapper + padding for the hint-chain resolution kernel."""
import jax
import jax.numpy as jnp
import numpy as np

from ..phash.ops import _pad_pow2
from ..pkval.kernel import MAX_PROBE
from .kernel import hintchain as _hintchain

hintchain = jax.jit(_hintchain, static_argnames=("root_id", "max_probe"))


def hintchain_resolve(client_idx, fallback_idx, name_hashes, depths, *,
                      root_id: int = 1, max_probe: int = MAX_PROBE
                      ) -> "tuple[np.ndarray, np.ndarray]":
    """Resolve a whole window's hint chains in ONE kernel launch.

    ``client_idx``/``fallback_idx`` are (parent, name_hash, value) array
    triples — ``HashIndex.arrays()`` snapshots of the client cache and the
    merged namenode caches.  ``name_hashes [N, D]`` / ``depths [N]``
    describe every op's component chain (depth 0 = never probed).  N is
    padded to a power of two so jit recompiles stay O(log N).  Returns the
    kernel's (child_ids, src) [N, D] encoding (see kernel module doc)."""
    nam = np.asarray(name_hashes, dtype=np.int64) & 0xFFFFFFFF
    dep = np.asarray(depths, dtype=np.int32)
    n = nam.shape[0]
    if n == 0:
        d0 = nam.shape[1] if nam.ndim == 2 else 0
        return (np.full((0, d0), -2, np.int32),
                np.full((0, d0), -1, np.int32))
    d = nam.shape[1]
    pn = _pad_pow2(n)
    nbuf = np.zeros((pn, d), np.uint32)
    nbuf[:n] = nam.astype(np.uint32)
    dbuf = np.zeros(pn, np.int32)
    dbuf[:n] = dep
    cp, cn_, cv = (np.asarray(a) for a in client_idx)
    fp, fn_, fv = (np.asarray(a) for a in fallback_idx)
    childs, srcs = hintchain(
        jnp.asarray(cp.astype(np.int32)), jnp.asarray(cn_.astype(np.uint32)),
        jnp.asarray(cv.astype(np.int32)), jnp.asarray(fp.astype(np.int32)),
        jnp.asarray(fn_.astype(np.uint32)), jnp.asarray(fv.astype(np.int32)),
        jnp.asarray(nbuf), jnp.asarray(dbuf), root_id=root_id,
        max_probe=max_probe)
    return np.asarray(childs)[:n], np.asarray(srcs)[:n]
