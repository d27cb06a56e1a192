"""Host time in the compiled step's call (the program's ``executor.call``
span: argument flatten, the feeds' host-to-device copy, PJRT enqueue), mean
over the window's runs, from the program's span ring."""

from step_spans import window_phase_ms


def reduce(run):
    return window_phase_ms(run, "SPAN_CALL")
