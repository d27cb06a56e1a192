"""Set-up's time in ``Executor.run`` on an in-memory compile-cache miss
(the program's ``executor.compile`` spans: build, trace, lower, compile or
load from JAX's cache, first enqueue), summed over the process. The window
has none: ``compiles_in_window.train`` counts them."""

from step_spans import process_span_s


def reduce(run):
    return process_span_s("SPAN_COMPILE")
