"""Latent attention's share of its roofline: the least time the chip could
take for one step's attention at TWO widths (192-wide queries and keys,
128-wide values: the larger of the causal pairs' FLOPs over peak FLOP/s and
the bytes over peak bytes/s, both from the family's ``attention_cost``) x
steps / the summed device time of the operations named ``attn_<tier>_fwd``
or ``attn_<tier>_bwd*`` in the traced window (``step_spans.ATTN_FWD`` /
``ATTN_BWD``: the only attention in this cell's step; recomputation is in
the time and not in the cost).

A ``benchmark`` PR that re-points ``attn_roofline.train`` at the ``attn_*``
kernels by name (``ROADMAP.md`` M2; today it sums every
``tpu_custom_call``, the delta rule's too) folds this metric into it."""

from step_spans import ATTN_BWD, ATTN_FWD, kernel_ops


def reduce(run):
    cost = getattr(run["family"], "attention_cost", None)
    if cost is None or run["peaks"] is None:
        return None
    found = [kernel_ops(run, pattern) for pattern in (ATTN_FWD, ATTN_BWD)]
    if not all(found):
        return None
    flops, nbytes = cost(run["cfg"], run["mix"])
    least = max(flops / run["peaks"]["flops_per_s"],
                nbytes / run["peaks"]["bytes_per_s"])
    return 100.0 * least * run["steps"] / sum(s for s, _ in found)
