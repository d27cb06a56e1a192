"""Set-up's time in the backend: XLA's (and Mosaic's) compile of an
executable that JAX's persistent cache did not hold (``jax.backend_compile``)
or its read back from that cache (``jax.cache_load``), inside the program's
``executor.compile`` spans, before the window.
``setup_backend_compiles.train`` says which of the two it was."""

from setup_spans import compile_stage_s


def reduce(run):
    return compile_stage_s(run, "SPAN_JAX_COMPILE", "SPAN_JAX_CACHE_LOAD")
