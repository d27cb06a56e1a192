"""``executor_compile_cache_miss_total`` after the window less before it."""


def reduce(run):
    return run["compiles_in_window"]
