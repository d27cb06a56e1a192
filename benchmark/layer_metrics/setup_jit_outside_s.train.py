"""Set-up's time in JAX compiling what is not the Executor's: the program's
``jax.*`` spans under run id 0 before the window opened (the driver's jitted
helpers - weights from the seed, norms; in ``transformer-big.train-wmt``
the dygraph eager trace's per-op compiles), nested ones counted once."""

from setup_spans import outside_runs_s


def reduce(run):
    return outside_runs_s(run)
