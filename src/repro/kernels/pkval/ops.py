"""jit'd wrapper + padding for the grouped PK-validation kernel."""
import jax
import numpy as np

from ..handover import to_device
from ..phash.ops import _pad_pow2
from .kernel import MAX_PROBE
from .kernel import pkval as _pkval

pkval = jax.jit(_pkval, static_argnames=("max_probe",))


def pkval_lookup(tp, tn, tv, parents, name_hashes, *,
                 max_probe: int = MAX_PROBE) -> np.ndarray:
    """Resolve a whole batch of (parent_id, name_hash) composite-PK probes
    against the columnar store's hash index in ONE kernel launch.

    ``tp``/``tn``/``tv`` are the index's parent/name-hash/value arrays
    (capacity a power of two; see ``repro.core.columnar.HashIndex``).
    Probes are padded to a power-of-two length with parent ``-1`` (always a
    miss) so jit recompiles stay O(log N).
    Returns ids [N] int32: resolved inode id, ``-1`` = no such row,
    ``-3`` = collided bucket (caller must fall back, not trust)."""
    par = np.asarray(parents, dtype=np.int64)
    nam = np.asarray(name_hashes, dtype=np.int64) & 0xFFFFFFFF
    n = par.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    pn = _pad_pow2(n)
    pbuf = np.full(pn, -1, np.int32)
    pbuf[:n] = par.astype(np.int32)
    nbuf = np.zeros(pn, np.uint32)
    nbuf[:n] = nam.astype(np.uint32)
    out = pkval(*to_device(np.asarray(tp, np.int32),
                           np.asarray(tn, np.uint32),
                           np.asarray(tv, np.int32), pbuf, nbuf),
                max_probe=max_probe)
    return np.asarray(out)[:n]
