"""Readers of the per-layer metrics. Each module has ``read(run, **args)``:
``run`` is what ``run.py`` gathered in a traced run (``trace``: the
reduction of ``reduce_trace.py``; ``counters``: compile clock and set-up
readings; ``memory``: device memory stats; ``input_bytes``, ``peaks``,
``chips``). A reader that finds nothing to read returns None and the metric
is left out of the line: never a 0 for something that was not measured."""
