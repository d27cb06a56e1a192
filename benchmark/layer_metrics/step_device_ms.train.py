"""Device busy time in the traced window / steps in it."""


def reduce(run):
    if run["trace"] is None:
        return None
    return 1e3 * run["trace"]["busy_s"] / run["steps"]
