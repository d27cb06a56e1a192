"""Calls a step of the Pallas attention's forward kernels in the traced
window: one a layer if the forward runs once, two if the ``autodiff`` op's
replay runs it again."""

from step_spans import ATTN_FWD, kernel_ops


def reduce(run):
    found = kernel_ops(run, ATTN_FWD)
    return found[1] / run["steps"] if found else None
