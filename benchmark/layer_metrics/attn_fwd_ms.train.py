"""Device time a step of the Pallas attention's forward kernels: the
traced window's operations named ``attn_<tier>_fwd`` / steps."""

from step_spans import ATTN_FWD, kernel_ops


def reduce(run):
    found = kernel_ops(run, ATTN_FWD)
    return 1e3 * found[0] / run["steps"] if found else None
