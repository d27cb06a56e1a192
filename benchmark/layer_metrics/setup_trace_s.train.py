"""Set-up's time in Python's walk of the program's ops into a jaxpr
(``_trace_step``, every ``lower_op``, every Pallas kernel body): the
program's ``jax.trace`` spans inside its ``executor.compile`` spans, before
the window, nested ones counted once."""

from setup_spans import compile_stage_s


def reduce(run):
    return compile_stage_s(run, "SPAN_JAX_TRACE")
