"""Host time inside ``Executor.run`` a step, over every call of the
window that fetched nothing: their summed wall time / their number (a
single call is too short for the host's clock)."""


def reduce(run):
    if not run["dispatch_calls"]:
        return None
    return 1e3 * run["dispatch_s"] / run["dispatch_calls"]
