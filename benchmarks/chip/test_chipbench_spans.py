"""The nested span reduction (``spantrace``) and the span report built on
it (``spanreport``): on synthetic events, self time subtracts a span's
children, an idle gap is split among the innermost spans covering it,
nesting is per thread, and the base numbers are ``devtrace``'s; on a real
CPU profile, metadata is stripped from names; on a tiny cell run on the
CPU, the report's readings come out; on an excerpt of a chip trace with
the program's spans (``testdata/span_excerpt.json``), idle time and self
time add up and each reading agrees with a count made from the events."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from devtrace import reduce as device_reduce
from spantrace import load_events, reduce, span_name


def host(name, a, b, thread=0):
    return {"plane": "/host:CPU", "line": "python3", "name": name,
            "start_ns": a, "dur_ns": b - a, "thread": thread}


def busy(a, b):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "%fusion",
            "start_ns": a, "dur_ns": b - a}


def nested():
    """One call: a planner window (lowering, with a pkval launch inside)
    then a namenode batch (one op on the sequential path inside), in a
    window of 1,100 ns."""
    return [host("window", 0, 1100), host("run_trace", 100, 900),
            host("planner.window", 150, 400),
            host("planner.lower", 160, 260), host("kernel.pkval", 200, 250),
            host("namenode.batch", 400, 800),
            host("namenode.single", 450, 500),
            busy(0, 100), busy(210, 240), busy(900, 1000)]


def _s(table):
    return {k: round(v * 1e9) for k, v in table.items()}


def test_self_time_subtracts_children():
    r = reduce(nested())
    assert _s(r["span_self_s"]) == {
        "run_trace": 800 - 250 - 400, "planner.window": 250 - 100,
        "planner.lower": 100 - 50, "kernel.pkval": 50,
        "namenode.batch": 400 - 50, "namenode.single": 50}
    assert _s(r["span_s"])["run_trace"] == 800


def test_idle_gaps_go_to_the_innermost_span():
    r = reduce(nested())
    # gaps [100, 210), [240, 900), [1000, 1100)
    assert _s(dict(r["idle_gaps"])) == {
        "run_trace": 50 + 100, "planner.window": 10 + 140,
        "planner.lower": 40 + 10, "kernel.pkval": 10 + 10,
        "namenode.batch": 50 + 300, "namenode.single": 50, "other": 100}
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    assert round(r["idle_program_s"] * 1e9) == 770 - 150
    assert _s(r["span_device_idle_s"]) == {
        "run_trace": 770, "planner.window": 250 - 30,
        "planner.lower": 100 - 30, "kernel.pkval": 50 - 30,
        "namenode.batch": 400, "namenode.single": 50}


def test_nesting_is_per_thread():
    ev = [host("window", 0, 1000), host("run_trace", 0, 1000, thread=0),
          host("namenode.batch", 100, 500, thread=1),
          host("namenode.single", 200, 300, thread=1)]
    r = reduce(ev)
    assert _s(r["span_self_s"]) == {"run_trace": 1000,
                                    "namenode.batch": 300,
                                    "namenode.single": 100}
    # the worker's spans started after run_trace: they are innermost
    assert _s(dict(r["idle_gaps"])) == {"run_trace": 600,
                                        "namenode.batch": 300,
                                        "namenode.single": 100}
    assert round(r["idle_program_s"] * 1e9) == 400


def test_equal_starts_nest_the_shorter_inside():
    ev = [host("window", 0, 100), host("namenode.batch", 10, 90),
          host("namenode.single", 10, 50)]
    r = reduce(ev)
    assert _s(r["span_self_s"]) == {"namenode.batch": 40,
                                    "namenode.single": 40}
    assert _s(dict(r["idle_gaps"])) == {"namenode.single": 40,
                                        "namenode.batch": 40, "other": 20}


def test_base_numbers_are_devtraces():
    ev = nested()
    base, r = device_reduce(ev), reduce(ev)
    for k in ("window_s", "busy_s", "kernel_s", "device_ops",
              "n_device_planes"):
        assert r[k] == base[k]
    assert reduce([e for e in ev if e["name"] != "window"]) is None


def test_harness_spans_alone_keep_their_idle():
    """Spans that never nest, gaps that cross no border between two of
    them: every gap goes where devtrace sends it."""
    ev = [host("window", 0, 1000), host("wait_arrivals", 0, 300),
          host("run_trace", 300, 800), host("dispatch", 800, 850),
          busy(300, 320), busy(800, 810), busy(850, 1000)]
    want = {"wait_arrivals": 300, "run_trace": 480, "dispatch": 40}
    assert _s(dict(reduce(ev)["idle_gaps"])) == want
    assert _s(dict(device_reduce(ev)["idle_gaps"])) == want


def test_load_keeps_program_spans_and_strips_metadata(tmp_path):
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("window"):
            with TraceAnnotation("run_trace"):
                with TraceAnnotation("planner.window", window=3):
                    jax.numpy.ones(8).sum().block_until_ready()
                with TraceAnnotation("unrelated.span"):
                    pass
    ev = load_events(str(tmp_path))
    host_names = {e["name"] for e in ev
                  if not e["plane"].startswith("/device:")}
    assert host_names == {"window", "run_trace", "planner.window"}
    assert all("thread" in e for e in ev
               if not e["plane"].startswith("/device:"))
    assert span_name("namenode.batch#window=3#") == "namenode.batch"
    assert span_name("kernel.pkval") == "kernel.pkval"


@pytest.fixture
def _no_kernel_bucket_warmup(monkeypatch):
    """Compiling every kernel bucket in the Pallas interpreter would take
    minutes on the CPU; the window compiles what it needs."""
    import warmup
    monkeypatch.setattr(warmup, "warm_kernels", lambda *a, **k: None)


def test_report_reads_a_tiny_traced_cell(_no_kernel_bucket_warmup):
    """The span report on a traced tiny cell on the CPU (no device
    plane: every moment of the window is idle)."""
    import spanreport
    from run_cell import run
    from tinycell import TINY_WARMUP_S, tiny
    with spanreport.spans_kept() as seen:
        result = run(tiny("spotify-1m.steady", trace=True), 2 ** 31 + 91,
                     1.5, True, {"hbm_bytes_per_s": 819e9},
                     warmup_s=TINY_WARMUP_S)
    assert result["correct"]
    got = spanreport.readings(seen.ctx, spanreport.held_s(seen))
    assert set(got) == {"planner.host_us_per_op", "namenode.host_us_per_op",
                        "kernels.h2d_kib_per_op",
                        "kernels.launch_idle_share", "client.held_ms_p50"}
    for name in ("planner.host_us_per_op", "namenode.host_us_per_op",
                 "client.held_ms_p50"):
        assert got[name] > 0
    assert got["kernels.h2d_kib_per_op"] >= 0
    t = seen.ctx.trace
    owners = {k for k, _ in t["idle_gaps"]}
    assert {"planner.window", "namenode.batch"} & owners
    assert 0 < t["idle_program_s"] <= t["span_device_idle_s"]["run_trace"]
    # the harness's readers see the same trace as without the spans
    assert result["metrics"]["device.idle_share.steady"]["value"] == 100.0


# -- a recorded excerpt -----------------------------------------------------
# testdata/span_excerpt.json: 450 ms from 20 s into a traced window of
# spotify-1m.steady on a TPU v5e (spanreport.py --excerpt), with every
# harness, program and device event overlapping it

SPAN_EXCERPT = Path(__file__).resolve().parent / "testdata" / \
    "span_excerpt.json"


@pytest.fixture(scope="module")
def span_events():
    return json.loads(SPAN_EXCERPT.read_text())["events"]


def _clipped(events, w0, w1):
    return [(max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1),
             e) for e in events
            if min(e["start_ns"] + e["dur_ns"], w1) > max(e["start_ns"], w0)]


def test_excerpt_idle_is_charged_to_program_spans(span_events):
    r = reduce(span_events)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    owners = dict(r["idle_gaps"])
    assert owners["planner.snapshot"] > 0.1      # the largest owner
    assert max(owners, key=owners.get) == "planner.snapshot"
    in_run = r["span_device_idle_s"]["run_trace"]
    assert 0.9 * in_run <= r["idle_program_s"] <= in_run


def test_excerpt_self_times_add_up_to_the_covered_time(span_events):
    """On one thread, self times partition the time some span covers."""
    r = reduce(span_events)
    w = [e for e in span_events if e["name"] == "window"][0]
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
    spans = sorted((a, b) for a, b, e in _clipped(span_events, w0, w1)
                   if not e["plane"].startswith("/device:")
                   and e["name"] != "window")
    covered, reach = 0, w0
    for a, b in spans:
        if b > reach:
            covered += b - max(a, reach)
            reach = b
    assert sum(r["span_self_s"].values()) == pytest.approx(covered / 1e9)
    assert r["kernel_s"] == device_reduce(span_events)["kernel_s"]


def test_excerpt_readings(span_events):
    """Each reading of spanreport on the excerpt, against a count made
    here from the events."""
    from spanreport import readings
    r = reduce(span_events)
    w = [e for e in span_events if e["name"] == "window"][0]
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
    ops = [(max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
           for e in span_events if e["line"] == "XLA Ops"]
    kernel_ns = idle_ns = 0
    for a, b, e in _clipped(span_events, w0, w1):
        if e["name"].startswith("kernel."):
            kernel_ns += b - a
            # a brute-force walk over the kernel span, 1 us at a time
            idle_ns += 1000 * sum(
                1 for t in range(a, b, 1000)
                if not any(x <= t < y for x, y in ops))
    ctx = SimpleNamespace(trace=r, served=[True] * 250 + [False] * 3,
                          counters={"planned_ops": 200,
                                    "pkval.h2d_bytes": 3 << 20,
                                    "treeagg.h2d_bytes": 1 << 20})
    got = readings(ctx, [0.2, 0.1, 0.4])
    planner = sum(v for k, v in r["span_self_s"].items()
                  if k.startswith("planner."))
    namenode = sum(v for k, v in r["span_self_s"].items()
                   if k.startswith("namenode."))
    assert got["planner.host_us_per_op"] == pytest.approx(
        1e6 * planner / 200)
    assert got["namenode.host_us_per_op"] == pytest.approx(
        1e6 * namenode / 250)
    assert got["kernels.h2d_kib_per_op"] == pytest.approx(4096 / 250)
    assert 0 <= got["kernels.launch_idle_share"] <= 100
    assert got["kernels.launch_idle_share"] == pytest.approx(
        100 * idle_ns / kernel_ns, abs=0.1)
    assert got["client.held_ms_p50"] == pytest.approx(200.0)
