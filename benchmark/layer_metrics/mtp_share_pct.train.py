"""The multi-token-prediction module's share of the device's busy time:
the traced window's instructions that ``fluid.profiler.newest_step_modules``
files under the module ``mtp`` (what the ops built inside
``fluid.name_scope("mtp")`` lowered to: the label embedding, ``eh_proj``,
the module's decoder layer and its pass through the shared head, forward,
replay and backward) / busy time. A program from before that table, a run
without a trace, or a step without such a module gives nothing to read:
``None``, never 0."""


def reduce(run):
    trace = run["trace"]
    if trace is None:
        return None
    from paddle_tpu.fluid import profiler

    table = getattr(profiler, "newest_step_modules", None)
    modules = table() if table is not None else None
    if not modules:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if modules.get(name) == "mtp")
    return 100.0 * seconds / trace["busy_s"] if seconds else None
