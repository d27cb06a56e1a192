"""Set-up's time from jaxpr to StableHLO (Mosaic's lowering of each
``pallas_call`` inside): the program's ``jax.lower`` spans inside its
``executor.compile`` spans, before the window."""

from setup_spans import compile_stage_s


def reduce(run):
    return compile_stage_s(run, "SPAN_JAX_LOWER")
