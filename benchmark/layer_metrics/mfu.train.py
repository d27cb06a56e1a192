"""The whole step's share of the chip's peak: model FLOPs a step from
shapes x steps / window seconds / (chips x peak FLOP/s)."""


def reduce(run):
    if run["peaks"] is None:
        return None
    return 100.0 * run["flops_per_step"] * run["steps"] / run["window_s"] / (
        run["chips"] * run["peaks"]["flops_per_s"])
