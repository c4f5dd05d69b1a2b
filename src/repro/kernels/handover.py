"""The one place a kernel wrapper hands host arrays to the device.

Every ``kernels/*/ops.py`` wrapper of the metadata path passes its padded
host arrays through :func:`to_device`, which counts their bytes on the
calling thread. The launch gate (``repro.core.namenode._with_phash_kernel``)
reads :func:`handed_bytes` around one launch and credits the difference
to the launching kernel family (``_KernelProbe.h2d_bytes``), so launches
on concurrent namenode threads never mix. Arrays that stay resident on the
device would stop being counted here.
"""
from __future__ import annotations

import threading
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_tls = threading.local()


def handed_bytes() -> int:
    """Bytes this thread has handed to the device so far."""
    return getattr(_tls, "n", 0)


def to_device(*arrays: np.ndarray) -> Tuple[jax.Array, ...]:
    """``jnp.asarray`` of each host array, counting its bytes."""
    _tls.n = handed_bytes() + sum(a.nbytes for a in arrays)
    return tuple(jnp.asarray(a) for a in arrays)
