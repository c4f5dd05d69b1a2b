"""Open-loop arithmetic on a fake clock with a stub service: latency runs
from each op's due time to the return of the call that served it, calls
take every op due (up to the cap), and ops due but never dispatched are
the backlog."""
import pytest

from openloop import drive, percentile


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_latency_from_due_time_to_call_return():
    clock = FakeClock()
    due = [0.0, 0.1, 0.15, 0.5, 0.9]
    calls = []

    def serve(lo, hi):
        calls.append((lo, hi, clock.t - 100.0))
        clock.t += 0.2                  # every call takes 200 ms
        return list(range(lo, hi))
    w = drive(due, serve, 1.0, cap=10, clock=clock, sleep=clock.sleep)
    # op 0 at 0.0 (returns 0.2); ops 1-2 due by 0.2 (return 0.4);
    # op 3 waits to 0.5 (returns 0.7); op 4 waits to 0.9 (returns 1.1)
    assert [(c.lo, c.hi) for c in w.calls] == [(0, 1), (1, 3), (3, 4),
                                               (4, 5)]
    assert w.latencies_s() == pytest.approx([0.2, 0.3, 0.25, 0.2, 0.2])
    assert w.answers == [0, 1, 2, 3, 4]
    assert w.backlog == 0
    assert w.seconds == pytest.approx(1.1)


def test_cap_and_backlog_when_overloaded():
    clock = FakeClock()
    due = [i * 0.01 for i in range(300)]      # 100 ops/s for 3 s

    def serve(lo, hi):
        clock.t += 0.5                  # 500 ms per call: overloaded
        return [None] * (hi - lo)
    w = drive(due, serve, 2.0, cap=20, clock=clock, sleep=clock.sleep)
    assert all(c.hi - c.lo <= 20 for c in w.calls)
    assert w.dispatched == 1 + 20 * (len(w.calls) - 1)
    assert w.backlog == sum(1 for d in due if d <= 2.0) - w.dispatched
    served = [i % 2 == 0 for i in range(w.dispatched)]
    assert len(w.latencies_s(served)) == sum(served)


def test_percentile_is_linear_between_order_statistics():
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile([5], 99) == 5
    assert percentile(list(range(101)), 99) == 99
