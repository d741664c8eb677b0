"""A counter or set-up reading of the run, by name."""


def read(run, name):
    return run["counters"].get(name)
