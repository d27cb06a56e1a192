"""Tokens per step x steps finished in the window / the window's wall
seconds: all the work over all the time."""


def reduce(run):
    return run["tokens_per_step"] * run["steps"] / run["window_s"]
