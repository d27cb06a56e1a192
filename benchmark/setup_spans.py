"""What the ``setup_*.train`` metrics share: reading what
``executor.compile`` is made of from the program's own span ring. The program turns JAX's report of each compile's stages into spans
(``fluid.profiler.JAX_SPANS``: ``jax.trace``, ``jax.lower``,
``jax.backend_compile`` or ``jax.cache_load``), for every jitted function
of the process: under an ``Executor.run``'s id inside that run, under run
id 0 outside any. Set-up ends where the window opens, which the ring says
itself: the newest ``run["steps"]`` run ids are the window's (nothing
calls ``Executor.run`` between the window's close and the readers) and the
first of their spans opens it; the reference's compiles come after. A
function traced inside another reports its span inside the outer one's, so
a time here is the union of a name's intervals, never their sum. A program
from before these spans gives every reader here nothing to read: ``None``,
never 0.
"""


def setup_jax_spans(run):
    """``(profiler, spans)``: the ring's ``jax.*`` spans that began before
    the window opened, ``(name, run_id, t_start, dur)`` oldest first; or
    ``None`` where the program records none (or the ring holds none)."""
    from paddle_tpu.fluid import profiler

    names = getattr(profiler, "JAX_SPANS", None)
    spans = profiler.recent_spans(names=names) if names else None
    if not spans:
        return None
    window = profiler.recent_spans(last_runs=run["steps"])
    opens = min(t for _, _, t, _ in window) if window else float("inf")
    return profiler, [s for s in spans if s[2] < opens]


def _union_s(profiler, spans):
    return profiler.union_seconds((t, t + dur) for _, _, t, dur in spans)


def compile_stage_s(run, *stages):
    """Seconds that the stages ``stages`` (the profiler's attributes:
    ``SPAN_JAX_TRACE``) took inside set-up's ``executor.compile`` spans:
    the spans of those names that carry a compiling run's id and lie
    inside its ``executor.compile`` interval."""
    found = setup_jax_spans(run)
    if found is None:
        return None
    profiler, spans = found
    names = {getattr(profiler, s) for s in stages}
    compiles = {run_id: (t, t + dur) for _, run_id, t, dur
                in profiler.recent_spans(names=[profiler.SPAN_COMPILE])}
    return _union_s(profiler, [
        s for s in spans if s[0] in names and s[1] in compiles
        and compiles[s[1]][0] <= s[2]
        and s[2] + s[3] <= compiles[s[1]][1]])


def outside_runs_s(run):
    """Seconds before the window that JAX traced, lowered, compiled or
    loaded what is not an ``Executor.run``'s (run id 0)."""
    found = setup_jax_spans(run)
    if found is None:
        return None
    profiler, spans = found
    return _union_s(profiler, [s for s in spans if s[1] == 0])


def backend_compiles(run):
    """How many executables the process had to compile before the window
    because JAX's persistent cache did not hold them. 0 is a count: the
    ring demonstrably holds ``jax.*`` spans."""
    found = setup_jax_spans(run)
    if found is None:
        return None
    profiler, spans = found
    return sum(1 for s in spans if s[0] == profiler.SPAN_JAX_COMPILE)
