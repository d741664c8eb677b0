"""The measured window: one client, closed loop, the cell's query back to
back. Pure control flow and arithmetic over an injected clock, so that the
tests can drive it with a fake one."""
import math
import time
from dataclasses import dataclass, field


@dataclass
class Window:
    starts: list = field(default_factory=list)   # clock at each query's start
    ends: list = field(default_factory=list)     # clock at its return
    answers: list = field(default_factory=list)  # what it returned, or None
    faults: list = field(default_factory=list)   # per query: why it failed, or None

    @property
    def attempted(self) -> int:
        return len(self.starts)

    @property
    def failed(self) -> int:
        return sum(f is not None for f in self.faults)

    @property
    def walls(self) -> list:
        return [e - s for s, e in zip(self.starts, self.ends)]

    @property
    def length_s(self) -> float:
        """First start to last return."""
        return self.ends[-1] - self.starts[0]


def run_window(query, seconds: float, clock=time.perf_counter,
               max_queries=None) -> Window:
    """Call ``query()`` back to back. A new query starts while the time since
    the first start is under ``seconds`` (and at least once); the window
    closes when the query in flight returns. ``query`` returns
    ``(answer, fault)``; an exception it raises is that query's fault."""
    w = Window()
    t0 = clock()
    now = t0
    while now - t0 < seconds or not w.starts:
        if max_queries is not None and len(w.starts) >= max_queries:
            break
        w.starts.append(now)
        try:
            answer, fault = query()
        except Exception as e:   # a failed query is counted, not fatal
            answer, fault = None, f"{type(e).__name__}: {e}"
        now = clock()
        w.ends.append(now)
        w.answers.append(answer)
        w.faults.append(fault)
    return w


def add_faults(w: Window, faults, host_fallbacks: int = 0):
    """What was found once the window had closed: ``faults`` per query (a
    string or None, e.g. of the plan it executed), and the host fallbacks
    the program counted over the whole window, each of which fails one query
    that has no fault yet (the counter does not say which)."""
    for i, fault in enumerate(faults):
        if fault and not w.faults[i]:
            w.faults[i] = fault
    for i in range(len(w.faults)):
        if host_fallbacks <= 0:
            break
        if not w.faults[i]:
            w.faults[i] = "host fallback in the window"
            host_fallbacks -= 1


def query_s(w: Window) -> float:
    """Window length over queries completed: all the work and all the time
    of the window, so a stall anywhere in it moves this."""
    return w.length_s / len(w.ends)


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p`` of
    the sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p * len(ordered)) - 1)]


def query_p95_s(w: Window) -> float:
    return percentile(w.walls, 0.95)
