"""The window's control flow and arithmetic, on a fake clock."""
import pytest

from benchmark import window


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def make(clock, walls, fail_at=()):
    """A query that advances the fake clock by the next wall."""
    it = iter(walls)
    n = {"i": 0}

    def query():
        i = n["i"]
        n["i"] += 1
        clock.now += next(it)
        if i in fail_at:
            raise RuntimeError("boom")
        return f"answer{i}", None
    return query


def test_query_starts_while_elapsed_under_seconds_and_window_closes_on_return():
    # Q1 at 24.9 s under --seconds 51: starts at 0, 24.9, 49.8; closes at 74.7
    clock = FakeClock()
    w = window.run_window(make(clock, [24.9] * 10), 51, clock)
    assert w.attempted == 3
    assert w.length_s == pytest.approx(74.7)
    assert window.query_s(w) == pytest.approx(24.9)
    assert w.answers == ["answer0", "answer1", "answer2"]


def test_at_least_one_query_whatever_the_seconds():
    clock = FakeClock()
    w = window.run_window(make(clock, [3.0] * 3), 0, clock)
    assert w.attempted == 1


def test_max_queries_caps_a_traced_window():
    clock = FakeClock()
    w = window.run_window(make(clock, [0.1] * 100), 5, clock, max_queries=1)
    assert w.attempted == 1


@pytest.mark.parametrize("metric", ["query_s", "query_p95_s"])
def test_an_injected_stall_moves_the_metric(metric):
    steady = [0.1] * 100
    stalled = [0.1] * 100
    stalled[40:46] = [2.0] * 6       # six slow queries: beyond the 95th
    values = []
    for walls in (steady, stalled):
        clock = FakeClock()
        w = window.run_window(make(clock, walls + [0.1] * 900), 10, clock)
        values.append(getattr(window, metric)(w))
    assert values[1] > 1.5 * values[0]


def test_one_stall_moves_query_s_but_not_the_p95():
    clock = FakeClock()
    walls = [0.1] * 50 + [5.0] + [0.1] * 900
    w = window.run_window(make(clock, walls), 10, clock)
    assert window.query_s(w) > 0.14          # 10.0 s over 51 queries or so
    assert window.query_p95_s(w) == pytest.approx(0.1)


def test_a_query_that_raises_is_counted_and_the_window_goes_on():
    clock = FakeClock()
    w = window.run_window(make(clock, [1.0] * 20, fail_at={2}), 5, clock)
    assert w.attempted == 5 and w.failed == 1
    assert w.answers[2] is None and "boom" in w.faults[2]


@pytest.mark.parametrize("values,p,want", [
    (list(range(1, 101)), 0.95, 95),
    (list(range(1, 21)), 0.95, 19),
    ([7.0], 0.95, 7.0),
    ([3, 1, 2], 0.5, 2),
])
def test_percentile_is_nearest_rank(values, p, want):
    assert window.percentile(values, p) == want


def test_faults_found_after_the_window_fail_their_queries():
    w = window.Window(starts=[0, 1, 2], ends=[1, 2, 3], answers=[1, 2, 3],
                      faults=[None, "raised", None])
    window.add_faults(w, [None, "host operator X", "host operator X"])
    assert w.faults == [None, "raised", "host operator X"] and w.failed == 2


@pytest.mark.parametrize("fallbacks,failed", [(0, 0), (1, 1), (2, 2), (9, 3)])
def test_each_host_fallback_of_the_window_fails_one_query(fallbacks, failed):
    w = window.Window(starts=[0, 1, 2], ends=[1, 2, 3], answers=[1, 2, 3],
                      faults=[None, None, None])
    window.add_faults(w, [None, None, None], fallbacks)
    assert w.failed == failed
