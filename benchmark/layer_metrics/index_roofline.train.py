"""The learned selection's share of its roofline: the least time the chip
could take for one step's selections (the larger of the FLOPs over peak
FLOP/s and the bytes over peak bytes/s, both from the family's
``index_cost``) x steps / the device time filed under ``sparse_index`` in
the traced window (recomputation is in the time and not in the cost). It
reads the op's scope, whatever implements it."""

from step_regions import region_seconds


def reduce(run):
    cost = getattr(run["family"], "index_cost", None)
    if cost is None or run["peaks"] is None:
        return None
    seconds = region_seconds(run, ("sparse_index",))
    if not seconds:
        return None
    flops, nbytes = cost(run["cfg"], run["mix"])
    least = max(flops / run["peaks"]["flops_per_s"],
                nbytes / run["peaks"]["bytes_per_s"])
    return 100.0 * least * run["steps"] / seconds
