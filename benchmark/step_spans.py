"""What the ``run_*_ms.train``, ``setup_compile_s.train`` and ``attn_*``
metrics share: reading the program's own span ring
(``paddle_tpu.fluid.profiler.recent_spans``: ``(name, run_id, t_start,
dur)`` on ``perf_counter``, one run id an ``Executor.run`` call) and the
traced window's operations by kernel name. A program from before the spans
and the names gives every reader here nothing to read: ``None``, never 0.
"""

import re


def _profiler():
    from paddle_tpu.fluid import profiler

    return profiler if hasattr(profiler, "recent_spans") else None


def mean_ms(spans, steps, window_s):
    """Mean duration in ms of ``spans``, which must be of exactly the
    window's ``steps`` runs and lie inside a stretch no longer than the
    window; else ``None``."""
    if not spans or len({run_id for _, run_id, _, _ in spans}) != steps:
        return None
    first = min(t for _, _, t, _ in spans)
    last = max(t + dur for _, _, t, dur in spans)
    if last - first > window_s:
        return None
    return 1e3 * sum(dur for _, _, _, dur in spans) / len(spans)


def window_phase_ms(run, span):
    """Mean of the ``Executor.run`` phase ``span`` (the profiler's
    attribute: ``SPAN_PREPARE``) over the window's runs: the newest
    ``run["steps"]`` run ids in the ring. Nothing calls ``Executor.run``
    between the window's close and the readers."""
    profiler = _profiler()
    if profiler is None:
        return None
    spans = profiler.recent_spans(names=[getattr(profiler, span)],
                                  last_runs=run["steps"])
    return mean_ms(spans, run["steps"], run["window_s"])


def process_span_s(span):
    """Summed duration in s of every ``span`` in the ring, or ``None``
    where it holds none."""
    profiler = _profiler()
    if profiler is None:
        return None
    spans = profiler.recent_spans(names=[getattr(profiler, span)])
    return sum(dur for _, _, _, dur in spans) if spans else None


def kernel_ops(run, pattern):
    """``(seconds, calls)`` in the traced window of the device operations
    whose instruction name matches ``pattern`` at its start
    (``attn_block_fwd.3``), or ``None`` where there is no trace or no
    such operation."""
    trace = run["trace"]
    if trace is None:
        return None
    names = [n for n in trace["op_seconds"] if re.match(pattern, n)]
    if not names:
        return None
    return (sum(trace["op_seconds"][n] for n in names),
            sum(trace["op_calls"][n] for n in names))


ATTN_FWD = r"attn_[a-z]+_fwd"
ATTN_BWD = r"attn_[a-z]+_bwd"
