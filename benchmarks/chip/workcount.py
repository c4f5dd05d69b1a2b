"""The least work a kernel call must do, counted from the work asked of
it, and the chip's peaks to divide it by.

A count reads the same whatever implements the kernel: bytes every
implementation must move for the call's inputs and outputs, never the
table's capacity or the kernel's loop count. The kernel counted here
does integer gathers and compares and uses no matrix unit, so its least
time is bytes over HBM bandwidth.

* pkval, per probe: 8 B of key in (parent id, name hash), 4 B of id out,
  and one 12-B index slot read (parent, name hash, value).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

PKVAL_BYTES_PER_PROBE = 8 + 4 + 12

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def pkval_bytes(probes: int) -> int:
    return probes * PKVAL_BYTES_PER_PROBE


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_percent(nbytes: int, device_s: Optional[float],
                     peak: dict) -> Optional[float]:
    """Least time (bytes over HBM bandwidth) as a share of the measured
    device time, in %; None where either side is missing."""
    if not nbytes or not device_s or device_s <= 0:
        return None
    return 100.0 * (nbytes / peak["hbm_bytes_per_s"]) / device_s
