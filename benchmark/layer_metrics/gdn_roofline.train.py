"""The gated delta rule's share of its roofline: the least time the chip
could take for one step's DeltaNet cores (the larger of the FLOPs over
peak FLOP/s and the bytes over peak bytes/s, both from the family's
``gdn_cost``) x steps / the device time filed under ``gated_delta_rule``
in the traced window (forward and backward; recomputation is in the time
and not in the cost)."""

from step_regions import region_seconds


def reduce(run):
    cost = getattr(run["family"], "gdn_cost", None)
    if cost is None or run["peaks"] is None:
        return None
    seconds = region_seconds(run, ("gated_delta_rule",))
    if not seconds:
        return None
    flops, nbytes = cost(run["cfg"], run["mix"])
    least = max(flops / run["peaks"]["flops_per_s"],
                nbytes / run["peaks"]["bytes_per_s"])
    return 100.0 * least * run["steps"] / seconds
