"""1 - busy / window on the busiest device of the traced window, in %."""


def read(run):
    t = run["trace"]
    if "busy_s_busiest" not in t:
        return None
    return 100.0 * (1.0 - t["busy_s_busiest"] / t["window_s"])
