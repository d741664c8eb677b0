"""The host path of a ``collect()`` by phase, from the program's own spans.

Every span of the program (``spark_rapids_tpu/utils/tracing.py``) books its
self time — duration minus what its child spans cover — to the phase totals
of the query it ran in, and the tracer keeps the totals of the last 256
queries (``get_tracer().recent_queries``): per phase name calls, seconds of
self time summed over threads, bytes; the query's wall; and the covered
wall, the union over all threads of the intervals of spans that do work.
This reader takes the newest ``run["trace"]["queries"]`` summaries — the
traced window's, since nothing calls ``collect()`` after it — and gives a
per-query mean:

- ``field`` ``self_s`` / ``calls`` / ``bytes``: summed over ``phases``;
- ``field`` ``unattributed_share``: 1 - covered wall / query wall, in %.

It reads nothing, never a number, unless it finds that many summaries and
their walls sum to the traced ``bench.collect`` spans' length within 2 %:
the summaries then are the window's. A program without the totals (before
its ``tracing`` PR) has nothing to read.

This file imports the program, inside ``read``: ``benchmark/README.md``
says only ``engine.py`` does, and that file may not be edited by the PR
that added this one (``PERF.md`` section 7 has the sentence to amend)."""

WALL_TOLERANCE = 0.02


def summaries_of(run, recent):
    """The newest ``run["trace"]["queries"]`` of ``recent`` (oldest first),
    or None where they cannot be the traced window's."""
    t = run["trace"]
    n = t.get("queries")
    if not n or "span_s" not in t or len(recent) < n:
        return None
    window = recent[-n:]
    walls = [q.get("wall_s") for q in window]
    if any(w is None for w in walls):
        return None
    if abs(sum(walls) - t["span_s"]) > WALL_TOLERANCE * t["span_s"]:
        return None
    return window


def read(run, phases=(), field="self_s"):
    from spark_rapids_tpu.utils.tracing import get_tracer
    recent_queries = getattr(get_tracer(), "recent_queries", None)
    if recent_queries is None:
        return None
    window = summaries_of(run, recent_queries(run["trace"].get("queries", 0)))
    if window is None:
        return None
    if field == "unattributed_share":
        wall = sum(q["wall_s"] for q in window)
        return 100.0 * (1.0 - sum(q["covered_s"] for q in window) / wall)
    total = sum(q["phases"].get(p, {}).get(field, 0)
                for q in window for p in phases)
    return total / len(window)
