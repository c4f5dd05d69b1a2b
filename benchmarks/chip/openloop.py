"""Open-loop driver: ops fall due on a fixed schedule whatever the system
does, and each op's latency runs from its due time to the return of the
call that served it.

Whenever the service is free, the driver hands it every op that has come
due, up to ``cap`` ops, in one call. When nothing is due it sleeps until
the next op is. It stops dispatching at ``seconds``; the call in flight
then finishes, and ops that came due but were never dispatched are the
backlog. The clock and the sleep are parameters, so the arithmetic can
be checked with a fake clock.
"""
from __future__ import annotations

import bisect
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence


@dataclass
class Call:
    lo: int            # first op index of the call
    hi: int            # one past its last op
    start: float       # seconds since the window opened
    end: float


@dataclass
class Window:
    due: Sequence[float]
    calls: List[Call] = field(default_factory=list)
    answers: List[Any] = field(default_factory=list)   # per dispatched op
    seconds: float = 0.0        # length of the window as measured
    backlog: int = 0            # ops due before the close, never dispatched

    @property
    def dispatched(self) -> int:
        return self.calls[-1].hi if self.calls else 0

    def latencies_s(self, served: Optional[Sequence[bool]] = None
                    ) -> List[float]:
        """Due-to-return latency of every dispatched op (or of those
        whose ``served`` flag is set)."""
        out: List[float] = []
        for c in self.calls:
            for i in range(c.lo, c.hi):
                if served is None or served[i]:
                    out.append(c.end - self.due[i])
        return out


def drive(due: Sequence[float], serve: Callable[[int, int], List[Any]],
          seconds: float, *, cap: int,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          on_call: Optional[Callable[[Call], None]] = None) -> Window:
    """Run ``serve(lo, hi)`` over ``[0, seconds)`` of the schedule ``due``
    (sorted offsets in seconds). ``serve`` returns one answer per op."""
    w = Window(due=due)
    t0 = clock()
    lo = 0
    n = len(due)
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        hi = min(bisect.bisect_right(due, now, lo), lo + cap)
        if hi == lo:
            nxt = due[lo] if lo < n else math.inf
            sleep(max(0.0, min(nxt, seconds) - now))
            continue
        answers = serve(lo, hi)
        if len(answers) != hi - lo:
            raise RuntimeError(f"{hi - lo} ops served, {len(answers)} "
                               f"answers returned")
        call = Call(lo, hi, now, clock() - t0)
        w.calls.append(call)
        w.answers.extend(answers)
        if on_call is not None:
            on_call(call)
        lo = hi
    end = clock() - t0
    w.seconds = max(end, seconds)
    w.backlog = bisect.bisect_right(due, seconds, lo) - lo
    return w


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100), linear between order statistics
    (numpy's default method)."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    i = int(math.floor(pos))
    j = min(i + 1, len(s) - 1)
    return s[i] + (s[j] - s[i]) * (pos - i)
