"""Host time in ``Executor.run`` up to the compile-cache lookup (the
program's ``executor.prepare`` span: the scans of the block's ops, feed
normalisation, the state list, the key, the state out of the scope), mean
over the window's runs, from the program's span ring."""

from step_spans import window_phase_ms


def reduce(run):
    return window_phase_ms(run, "SPAN_PREPARE")
