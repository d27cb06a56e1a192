"""The select tier's share of its roofline: the least time the chip could
take for the KEPT pairs of one step's attention (the larger of their
FLOPs over peak FLOP/s and the bytes over peak bytes/s, both from the
family's ``attention_cost``) x steps / the summed device time of the
operations named ``attn_select_*`` in the traced window (recomputation is
in the time and not in the cost). A masked dense kernel computes every
causal pair, so it reads at most the kept share of what its matmuls
reach."""

from step_spans import kernel_ops

ATTN_SELECT = r"attn_select_"


def reduce(run):
    cost = getattr(run["family"], "attention_cost", None)
    if cost is None or run["peaks"] is None:
        return None
    found = kernel_ops(run, ATTN_SELECT)
    if not found:
        return None
    flops, nbytes = cost(run["cfg"], run["mix"])
    least = max(flops / run["peaks"]["flops_per_s"],
                nbytes / run["peaks"]["bytes_per_s"])
    return 100.0 * least * run["steps"] / found[0]
