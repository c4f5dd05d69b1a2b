"""Named host spans at the program's layer boundaries.

:func:`span` is ``jax.profiler.TraceAnnotation``: while a profiler runs,
each span is one event in the profiler's host trace, on the same clock as
the device's events, so a trace reducer can nest the program's spans under
a caller's own annotations and charge device idle time to the innermost
one. While no profiler runs a span costs well under a microsecond, so the
spans are always on. Names are ``<layer>.<what>``; the layers are
``planner``, ``namenode``, ``kernel`` and ``client``:

* ``planner.window`` (metadata ``window``: the window's sequence number),
  with ``planner.lower``, ``planner.snapshot`` (metadata
  ``snapshot_rebuilds`` and ``snapshot_delta_keys``: how its hint-cache
  snapshots were brought up to date), ``planner.validate`` and
  ``planner.deal`` inside it; ``planner.absorb`` after the window ran;
* ``namenode.batch``: one batch a pipeline hands to a namenode (metadata
  ``window`` where the batch was planned), with ``namenode.read_run``,
  ``namenode.write_run``, ``namenode.single`` (one op on the sequential
  path) and ``namenode.piggyback`` inside it; a subtree operation's
  ``namenode.subtree_lock`` (phase 1), ``namenode.subtree_scan`` (one
  phase-2 page of a directory's children, on the thread that scans it),
  ``namenode.subtree_wave`` (one advisory wave launch) and
  ``namenode.subtree_chunk`` (one phase-3 chunk commit);
* ``kernel.<family>`` around one kernel launch: host staging, the copy to
  the device, the device run and the copy back;
* ``client.finalize``: the pipeline's cost roll-up after its last window.

Metadata is for events that cross threads and for the snapshot's upkeep;
the other hot spans carry none.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span"]

#: ``span(name, **meta)`` -- a context manager; ``meta`` values are
#: recorded on the event while a profiler runs
span = TraceAnnotation
