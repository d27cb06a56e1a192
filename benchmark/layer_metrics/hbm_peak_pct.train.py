"""``peak_bytes_in_use`` / ``bytes_limit`` after the window."""


def reduce(run):
    mem = run["memory"]
    if not mem.get("bytes_limit"):
        return None
    return 100.0 * mem["peak_bytes_in_use"] / mem["bytes_limit"]
