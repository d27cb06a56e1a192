"""What the ``gdn_*`` and ``moe_*`` metrics share: the traced window's
device time filed under program op types. The device trace names an
instruction (``fusion.312``) and carries no ``op_name``, so the program's
``fluid.profiler.newest_step_regions`` says which program op an
instruction of the newest compiled step (the training step) came from,
forward and backward alike. A program from before that table, a run
without a trace, or a step without such ops gives nothing to read:
``None``, never 0.
"""


def region_seconds(run, op_types):
    """Summed device seconds in the traced window of the instructions
    filed under the program op types ``op_types``, or ``None``."""
    trace = run["trace"]
    if trace is None:
        return None
    from paddle_tpu.fluid import profiler

    table = getattr(profiler, "newest_step_regions", None)
    regions = table() if table is not None else None
    if not regions:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if regions.get(name, ("", ""))[1] in op_types)
    return seconds or None


GDN_OPS = ("gated_delta_rule", "causal_conv1d")
MOE_OPS = ("moe_route", "moe_experts")
