"""Persistent-cache hits over compile requests during set-up, in %. A
set-up that asked for no compile has no share to report."""


def read(run):
    c = run["counters"]
    if not c.get("setup_xla_compiles"):
        return None
    return 100.0 * c["setup_xla_cache_hits"] / c["setup_xla_compiles"]
