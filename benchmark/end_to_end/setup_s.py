"""Process start to window open: imports, program build, weights, compile
or cache load, the first steps and the warm-up."""


def reduce(run):
    return run["setup_s"]
