"""jit'd wrapper + padding for the subtree wave-expansion kernel."""
import jax
import numpy as np

from ..handover import to_device
from ..phash.ops import _pad_pow2
from .kernel import treeagg as _treeagg

#: wave padding sentinel — larger than any real inode id, keeps the
#: sorted wave sorted, and slot parents can never equal it
WAVE_PAD = np.int32(np.iinfo(np.int32).max)

treeagg = jax.jit(_treeagg)


def treeagg_expand(wave, par, isdir, size):
    """Resolve one BFS wave against the whole inode column set in ONE
    kernel launch.

    ``wave`` is the wave's directory ids (sorted ascending, unique);
    ``par``/``isdir``/``size`` are the columnar table's hot columns
    (cleared slots carry parent ``-1`` and never match).  Both sides are
    padded to a power of two — wave with :data:`WAVE_PAD`, slots with
    parent ``-1`` — so jit recompiles stay O(log N).  Returns numpy
    ``(seg [C], counts [W], dirs [W], sizes [W])`` int32, sliced back to
    the unpadded lengths."""
    wave = np.asarray(wave, dtype=np.int64)
    par = np.asarray(par, dtype=np.int64)
    w = wave.shape[0]
    c = par.shape[0]
    if w == 0 or c == 0:
        return (np.full(c, -1, np.int32), np.zeros(w, np.int32),
                np.zeros(w, np.int32), np.zeros(w, np.int32))
    pw = _pad_pow2(w)
    wbuf = np.full(pw, WAVE_PAD, np.int32)
    wbuf[:w] = wave.astype(np.int32)
    pc = _pad_pow2(c)
    pbuf = np.full(pc, -1, np.int32)
    pbuf[:c] = par.astype(np.int32)
    dbuf = np.zeros(pc, np.int32)
    dbuf[:c] = np.asarray(isdir, dtype=np.int64).astype(np.int32)
    sbuf = np.zeros(pc, np.int32)
    sbuf[:c] = np.asarray(size, dtype=np.int64).astype(np.int32)
    seg, cnt, dirs, szs = treeagg(*to_device(wbuf, pbuf, dbuf, sbuf))
    return (np.asarray(seg)[:c], np.asarray(cnt)[:w],
            np.asarray(dirs)[:w], np.asarray(szs)[:w])
