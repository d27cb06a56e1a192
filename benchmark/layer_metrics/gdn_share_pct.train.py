"""The linear-attention ops' share of the device's busy time: the traced
window's instructions filed under ``gated_delta_rule`` and
``causal_conv1d``, forward and backward / busy time."""

from step_regions import GDN_OPS, region_seconds


def reduce(run):
    seconds = region_seconds(run, GDN_OPS)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
