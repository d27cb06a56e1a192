"""Host time after the call (the program's ``executor.commit`` span:
anomaly scan, rng and state back into the scope, save ops, checkpoint,
preemption check), mean over the window's runs, from the program's span
ring."""

from step_spans import window_phase_ms


def reduce(run):
    return window_phase_ms(run, "SPAN_COMMIT")
