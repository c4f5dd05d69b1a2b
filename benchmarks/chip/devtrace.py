"""Device-trace capture and its reduction to busy time, per-kernel device
time and idle gaps attributed to what the host was doing.

The profiler writes an ``.xplane.pb``; :func:`load_events` flattens it to
plain event records (plane, line, name, start_ns, dur_ns) -- device-plane
events, and the host spans this harness writes with
``jax.profiler.TraceAnnotation`` (:data:`HOST_SPANS`). :func:`reduce`
works on those records only, so it can be checked on a small recorded
excerpt (``testdata/``) without a chip.

Reduction rules:

* the traced window is the host span ``window``;
* busy time is the union of the intervals of the device's op events (the
  ``XLA Ops`` line; the ``XLA Modules`` line where a trace has no op
  line), clipped to the window;
* a kernel's device time is the summed duration of the ``XLA Modules``
  events named after its jitted function (``jit_<kernel>``, with any
  ``(...)`` suffix dropped). A kernel with no event gets no entry -- its
  time is unknown, never 0;
* every idle gap of the device inside the window is charged to the host
  span that covers most of it (``other`` where none does).
"""
from __future__ import annotations

import bisect
import contextlib
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

#: host spans the harness writes, innermost last where they nest
HOST_SPANS = ("window", "dispatch", "wait_arrivals", "run_trace",
              "reference_check")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@contextlib.contextmanager
def maybe_annotate(name: str, enabled: bool) -> Iterator[None]:
    if not enabled:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def profile_options():
    """The profiler's options for a traced window: device ops and the
    ``TraceAnnotation`` spans (its host tracer), without its Python
    tracer. That one, on by default, records every Python call and holds
    it in memory until the trace stops -- some 340 B a call, tens of GiB
    over a subtree op on a million-file directory -- and nothing here
    reads it."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load_events(log_dir: str) -> List[dict]:
    """Flatten the profile under ``log_dir`` to event records."""
    from jax.profiler import ProfileData
    pbs = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(pbs[-1]))
    out: List[dict] = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for ev in line.events:
                if device or ev.name in HOST_SPANS:
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name,
                                "start_ns": int(ev.start_ns),
                                "dur_ns": int(ev.duration_ns)})
    return out


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def kernel_name(module_event: str) -> str:
    """``jit_pkval(123)`` -> ``pkval``; other names unchanged."""
    name = module_event.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def reduce(events: Sequence[dict]) -> Optional[dict]:
    """Busy/window seconds, per-kernel device seconds, top device modules
    and idle gaps by host span; None when the window span is missing."""
    windows = [e for e in events if e["name"] == "window"
               and not e["plane"].startswith("/device:")]
    if not windows:
        return None
    w0 = min(e["start_ns"] for e in windows)
    w1 = max(e["start_ns"] + e["dur_ns"] for e in windows)
    dev = [e for e in events if e["plane"].startswith("/device:")]
    planes = sorted({e["plane"] for e in dev})
    busy_ns = 0
    all_busy: List[Tuple[int, int]] = []
    for p in planes:
        mine = [e for e in dev if e["plane"] == p]
        line = OP_LINE if any(e["line"] == OP_LINE for e in mine) \
            else MODULE_LINE
        iv = [(max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
              for e in mine if e["line"] == line]
        u = _union([(a, b) for a, b in iv if b > a])
        busy_ns += sum(b - a for a, b in u)
        all_busy.extend(u)
    n_planes = max(1, len(planes))
    kernel_ns: Dict[str, int] = {}
    for e in dev:
        if e["line"] != MODULE_LINE:
            continue
        a = max(e["start_ns"], w0)
        b = min(e["start_ns"] + e["dur_ns"], w1)
        if b > a:
            k = kernel_name(e["name"])
            kernel_ns[k] = kernel_ns.get(k, 0) + (b - a)
    # idle gaps of the device, charged to the host span covering most of
    # each (the harness's spans follow one another, they do not nest)
    host = sorted(((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                   for e in events if not e["plane"].startswith("/device:")
                   and e["name"] != "window"))
    starts = [h[0] for h in host]
    gaps: List[Tuple[int, int]] = []
    cursor = w0
    for a, b in _union(all_busy):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if w1 > cursor:
        gaps.append((cursor, w1))
    idle_by_span: Dict[str, int] = {}
    for a, b in gaps:
        best, best_ns = "other", 0
        k = max(0, bisect.bisect_right(starts, a) - 1)
        while k < len(host) and host[k][0] < b:
            ov = min(b, host[k][1]) - max(a, host[k][0])
            if ov > best_ns:
                best, best_ns = host[k][2], ov
            k += 1
        idle_by_span[best] = idle_by_span.get(best, 0) + (b - a)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / n_planes / 1e9,
        "kernel_s": {k: v / n_planes / 1e9 for k, v in kernel_ns.items()},
        "device_ops": sorted(([k, v / n_planes / 1e9]
                              for k, v in kernel_ns.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v / n_planes / 1e9]
                             for k, v in idle_by_span.items()),
                            key=lambda kv: -kv[1])[:10],
        "n_device_planes": len(planes),
    }
