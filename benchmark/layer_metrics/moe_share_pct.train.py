"""The expert layer's share of the device's busy time: the traced
window's instructions filed under ``moe_route`` and ``moe_experts``,
forward and backward / busy time. The shared expert is plain ``mul`` ops
and is not in it."""

from step_regions import MOE_OPS, region_seconds


def reduce(run):
    seconds = region_seconds(run, MOE_OPS)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
