"""Device time a step of the Pallas attention's backward kernels: the
traced window's operations named ``attn_<tier>_bwd*`` / steps."""

from step_spans import ATTN_BWD, kernel_ops


def reduce(run):
    found = kernel_ops(run, ATTN_BWD)
    return 1e3 * found[0] / run["steps"] if found else None
