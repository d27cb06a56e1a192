"""Executables the whole process compiled before the window opened because
JAX's persistent cache did not hold them (the program's
``jax.backend_compile`` spans, whoever jitted). 0 on a warm machine."""

from setup_spans import backend_compiles


def reduce(run):
    return backend_compiles(run)
