"""The trace reduction, on an excerpt of a trace recorded on a TPU v5e
(``testdata/trace_excerpt.json``): busy time is the union of the device's
op intervals inside the window span, a kernel's time is the sum of its
``jit_<kernel>`` module events, a kernel with no event has no entry, and
every idle gap is charged to a host span or to ``other``."""
import json
from pathlib import Path

import pytest

from devtrace import HOST_SPANS, kernel_name, reduce

EXCERPT = Path(__file__).resolve().parent / "testdata" / "trace_excerpt.json"


@pytest.fixture(scope="module")
def events():
    return json.loads(EXCERPT.read_text())["events"]


def _window(events):
    w = [e for e in events if e["name"] == "window"][0]
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def test_busy_is_the_union_of_device_ops(events):
    w0, w1 = _window(events)
    # independent count: walk the op intervals in start order, counting
    # only time past the furthest end seen so far
    ops = sorted((max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
                 for e in events if e["line"] == "XLA Ops")
    busy, reach = 0, w0
    for a, b in ops:
        if b > reach:
            busy += b - max(a, reach)
            reach = b
    r = reduce(events)
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]


def test_kernel_time_from_module_events(events):
    w0, w1 = _window(events)
    r = reduce(events)
    want = {}
    for e in events:
        if e["line"] == "XLA Modules":
            k = kernel_name(e["name"])
            a = max(e["start_ns"], w0)
            b = min(e["start_ns"] + e["dur_ns"], w1)
            want[k] = want.get(k, 0) + max(0, b - a)
    assert set(r["kernel_s"]) == {"treeagg", "hintchain", "pkval"}
    for k, ns in want.items():
        assert r["kernel_s"][k] == pytest.approx(ns / 1e9)
    assert r["kernel_s"].get("phash_chain") is None    # no event: no entry
    assert [k for k, _ in r["device_ops"]][0] == "treeagg"


def test_idle_gaps_add_up_and_are_named(events):
    r = reduce(events)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    assert {k for k, _ in r["idle_gaps"]} <= set(HOST_SPANS) | {"other"}
    assert len(r["idle_gaps"]) <= 10 and len(r["device_ops"]) <= 10


def test_no_window_span_gives_nothing(events):
    assert reduce([e for e in events if e["name"] != "window"]) is None


def test_kernel_name():
    assert kernel_name("jit_pkval(8156105431190824860)") == "pkval"
    assert kernel_name("jit_treeagg") == "treeagg"
    assert kernel_name("fusion.3") == "fusion.3"


def _host_event_names(options, tmp_path):
    """Names of the host events a trace records around 200 Python calls
    inside a ``window`` span."""
    import jax
    from jax.profiler import ProfileData

    def step(i):
        return i + 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            s = 0
            for _ in range(200):
                s = step(s)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    return [ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.mark.parametrize("harness", [False, True])
def test_traced_window_records_spans_without_python_calls(harness, tmp_path):
    """The profiler's default options record one event per Python call
    (``$file:line name``), which a long window cannot hold; the harness's
    options record the spans alone."""
    import jax
    from devtrace import profile_options
    opts = profile_options() if harness else jax.profiler.ProfileOptions()
    names = _host_event_names(opts, tmp_path)
    assert "window" in names
    calls = [n for n in names if n.startswith("$") and n.endswith(" step")]
    assert len(calls) == (0 if harness else 200)
