"""A time of the trace reduction, optionally per traced query."""


def read(run, field, per_query=False):
    value = run["trace"].get(field)
    if value is None:
        return None
    return value / run["trace"]["queries"] if per_query else value
