"""Host time per query, seen from outside: the length of the traced
``bench.collect`` spans minus the time the busiest device was busy inside
them. What is left is planner, host scan, H2D/D2H waits, dispatch."""


def read(run):
    t = run["trace"]
    if "busy_in_spans_s" not in t:
        return None
    return (t["span_s"] - t["busy_in_spans_s"]) / t["queries"]
