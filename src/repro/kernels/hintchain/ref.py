"""Numpy oracle for the hint-chain resolution kernel (also its fallback)."""
import numpy as np

from ..pkval.kernel import MAX_PROBE
from ..pkval.ref import pkval_ref


def hintchain_ref(cp, cn, cv, fp, fn, fv, name_hashes, depths, *,
                  root_id: int = 1, max_probe: int = MAX_PROBE):
    """Bit-identical host walk of every chain: (child_ids, src) [N, D]."""
    cp = np.asarray(cp).astype(np.int32)
    cn = np.asarray(cn).astype(np.uint32)
    cv = np.asarray(cv).astype(np.int32)
    fp = np.asarray(fp).astype(np.int32)
    fn = np.asarray(fn).astype(np.uint32)
    fv = np.asarray(fv).astype(np.int32)
    nam = np.asarray(name_hashes).astype(np.uint32)
    dep = np.asarray(depths).astype(np.int32)
    n, d_max = nam.shape
    parent = np.full(n, root_id, np.int32)
    alive = dep > 0
    childs = np.full((n, d_max), -2, np.int32)
    srcs = np.full((n, d_max), -1, np.int32)
    for d in range(d_max):
        probing = alive & (np.int32(d) < dep)
        nd = nam[:, d]
        cval = pkval_ref(cp, cn, cv, parent, nd, max_probe=max_probe)
        fval = pkval_ref(fp, fn, fv, parent, nd, max_probe=max_probe)
        val = np.where(cval != np.int32(-1), cval, fval)
        found = probing & (val > 0)
        childs[:, d] = np.where(probing, val, np.int32(-2))
        srcs[:, d] = np.where(found & (cval > 0), np.int32(0),
                              np.where(found, np.int32(1), np.int32(-1)))
        parent = np.where(found, val, parent).astype(np.int32)
        alive = alive & found
    return childs, srcs
