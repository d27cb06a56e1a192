"""What the compiled training step holds in device memory as the compiler
sized it - arguments + outputs - the donated state + temporaries + code
(``fluid.profiler.newest_step_memory()["total"]``) - over the allocator's
``bytes_limit``. ``hbm_peak_pct.train`` reads the allocator's peak, which
leaves a program's temporaries out. ``None`` on a program from before
``newest_step_memory`` or where the step cannot be lowered again."""


def reduce(run):
    from paddle_tpu.fluid import profiler

    limit = run["memory"].get("bytes_limit")
    reader = getattr(profiler, "newest_step_memory", None)
    memory = reader() if reader is not None and limit else None
    if not memory:
        return None
    return 100.0 * memory["total"] / limit
