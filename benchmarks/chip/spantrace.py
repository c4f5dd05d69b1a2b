"""The program's own spans in a device trace, nested under the harness's.

``devtrace`` keeps the harness's spans only (``HOST_SPANS``) and charges
each idle gap of the device to the host span covering most of it. The
program writes spans too (``repro.core.spans``), on the same profiler
clock, and they nest inside the harness's ``run_trace``. This module
keeps both: :func:`load_events` also takes host events whose name starts
with one of :data:`PROGRAM_SPAN_PREFIXES` (any ``#...#`` metadata suffix
stripped), and records each host event's thread (its line's index in the
plane, since threads can share a line name). :func:`reduce` returns
``devtrace.reduce``'s busy, window and kernel times unchanged, with:

* ``span_s``: per span name, its time inside the window;
* ``span_self_s``: the same, less the time its child spans on the same
  thread cover;
* ``span_device_idle_s``: per span name, the part of its time inside the
  window in which the device ran no op;
* ``idle_program_s``: the device's idle time inside the window that some
  program span covers;
* ``idle_gaps``: every part of an idle gap charged to the innermost host
  span covering it -- the covering span that started last -- or to
  ``other`` where none does, every owner listed. On spans that never nest
  (the harness's alone) this differs from ``devtrace``'s rule only where
  a gap spans the border of two adjacent spans.

Idle times are divided by the number of device planes, as ``devtrace``
divides its idle gaps.
"""
from __future__ import annotations

import bisect
import heapq
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from devtrace import HOST_SPANS, MODULE_LINE, OP_LINE, _union
from devtrace import reduce as device_reduce

#: name prefixes of the program's spans (``repro.core.spans``)
PROGRAM_SPAN_PREFIXES = ("planner.", "namenode.", "kernel.", "client.")


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_SPAN_PREFIXES)


def span_name(raw: str) -> str:
    """A host event's name without its ``#key=value,...#`` metadata."""
    return raw.split("#", 1)[0]


def load_events(log_dir: str) -> List[dict]:
    """``devtrace.load_events``'s records plus the program's spans; host
    records carry ``thread``."""
    from jax.profiler import ProfileData
    pbs = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(pbs[-1]))
    out: List[dict] = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for thread, line in enumerate(plane.lines):
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for ev in line.events:
                rec = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": int(ev.start_ns),
                       "dur_ns": int(ev.duration_ns)}
                if not device:
                    rec["name"] = span_name(ev.name)
                    if rec["name"] not in HOST_SPANS \
                            and not is_program_span(rec["name"]):
                        continue
                    rec["thread"] = thread
                out.append(rec)
    return out


def _busy_union(dev: Sequence[dict], w0: int, w1: int
                ) -> List[Tuple[int, int]]:
    """Union over every device plane of its op intervals (the op line,
    else the module line), clipped to the window."""
    busy: List[Tuple[int, int]] = []
    for p in sorted({e["plane"] for e in dev}):
        mine = [e for e in dev if e["plane"] == p]
        line = OP_LINE if any(e["line"] == OP_LINE for e in mine) \
            else MODULE_LINE
        busy.extend((max(e["start_ns"], w0),
                     min(e["start_ns"] + e["dur_ns"], w1))
                    for e in mine if e["line"] == line)
    return _union([(a, b) for a, b in busy if b > a])


def _self_ns(spans: Sequence[Tuple[int, int, str, tuple]]
             ) -> Dict[str, int]:
    """Per name, each span's length less its direct children's, nesting
    taken within one thread only."""
    out: Dict[str, int] = {}
    for thread in sorted({s[3] for s in spans}):
        stack: List[List] = []            # [end, name, self_ns]
        for a, b, name, _ in sorted((s for s in spans if s[3] == thread),
                                    key=lambda s: (s[0], -s[1])):
            while stack and stack[-1][0] <= a:
                _, n, own = stack.pop()
                out[n] = out.get(n, 0) + own
            if stack:
                stack[-1][2] -= b - a
            stack.append([b, name, b - a])
        for _, n, own in stack:
            out[n] = out.get(n, 0) + own
    return out


def _innermost(spans: Sequence[Tuple[int, int, str, tuple]]
               ) -> List[Tuple[int, int, Optional[str], bool]]:
    """The window cut where any span starts or ends: each piece with the
    name of the innermost covering span (the one that started last, the
    shorter on a tie; None where none covers it) and whether a program
    span covers it."""
    bounds = sorted({t for s in spans for t in s[:2]})
    starts = sorted(range(len(spans)), key=lambda i: spans[i][0])
    heap: List[Tuple[int, int, int]] = []     # (-start, end, index)
    ends: Dict[int, int] = {}                 # end -> open program spans
    k = n_prog = 0
    out: List[Tuple[int, int, Optional[str], bool]] = []
    for a, b in zip(bounds, bounds[1:]):
        n_prog -= ends.pop(a, 0)
        while k < len(starts) and spans[starts[k]][0] <= a:
            i = starts[k]
            heapq.heappush(heap, (-spans[i][0], spans[i][1], i))
            if is_program_span(spans[i][2]):
                n_prog += 1
                ends[spans[i][1]] = ends.get(spans[i][1], 0) + 1
            k += 1
        # the top started last; an ended span is dropped once on top
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        out.append((a, b, spans[heap[0][2]][2] if heap else None,
                    n_prog > 0))
    return out


def reduce(events: Sequence[dict]) -> Optional[dict]:
    """``devtrace.reduce`` with the nested keys (module doc); None when
    the window span is missing."""
    out = device_reduce(events)
    if out is None:
        return None
    windows = [e for e in events if e["name"] == "window"
               and not e["plane"].startswith("/device:")]
    w0 = min(e["start_ns"] for e in windows)
    w1 = max(e["start_ns"] + e["dur_ns"] for e in windows)
    dev = [e for e in events if e["plane"].startswith("/device:")]
    n_planes = max(1, out["n_device_planes"])
    busy = _busy_union(dev, w0, w1)
    spans = [(max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1),
              e["name"], (e["plane"], e["line"], e.get("thread", 0)))
             for e in events if not e["plane"].startswith("/device:")
             and e["name"] != "window"]
    spans = [s for s in spans if s[1] > s[0]]

    b_starts = [a for a, _ in busy]
    b_cum = [0]
    for a, b in busy:
        b_cum.append(b_cum[-1] + b - a)

    def busy_before(t: int) -> int:
        i = bisect.bisect_right(b_starts, t) - 1
        if i < 0:
            return 0
        return b_cum[i] + min(t, busy[i][1]) - busy[i][0]

    span_ns: Dict[str, int] = {}
    idle_in: Dict[str, int] = {}
    for a, b, name, _ in spans:
        span_ns[name] = span_ns.get(name, 0) + (b - a)
        idle = (b - a) - (busy_before(b) - busy_before(a))
        idle_in[name] = idle_in.get(name, 0) + idle

    gaps: List[Tuple[int, int]] = []
    cursor = w0
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if w1 > cursor:
        gaps.append((cursor, w1))
    by_owner: Dict[str, int] = {}
    program_ns = 0
    pieces = _innermost(spans)
    j = 0
    for a, b in gaps:
        covered = 0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, owner, prog = pieces[k]
            ov = min(b, pb) - max(a, pa)
            if ov > 0 and owner is not None:
                by_owner[owner] = by_owner.get(owner, 0) + ov
                covered += ov
                if prog:
                    program_ns += ov
            k += 1
        if b - a > covered:
            by_owner["other"] = by_owner.get("other", 0) + (b - a - covered)

    out["span_s"] = {k: v / 1e9 for k, v in span_ns.items()}
    out["span_self_s"] = {k: v / 1e9 for k, v in _self_ns(spans).items()}
    out["span_device_idle_s"] = {k: v / n_planes / 1e9
                                 for k, v in idle_in.items()}
    out["idle_program_s"] = program_ns / n_planes / 1e9
    out["idle_gaps"] = sorted(([k, v / n_planes / 1e9]
                               for k, v in by_owner.items()),
                              key=lambda kv: -kv[1])
    return out
