"""The repo's benchmark: the yardstick later PRs are measured with.

Everything here is read-only for later PRs: they add files (a traffic mix, a
configuration, a metric, a reader, a reference) and entries in
``BENCHMARK.json``; they never edit one. See ``README.md``.
"""
