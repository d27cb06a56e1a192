"""The seven readers PR 26 added, each on a synthetic ``run``: the right
value, and ``None`` (never 0) where there is nothing to read - no trace,
a ring short of the window's steps, no ``attn_*`` operation. Not tier-1:
run by hand, ``python -m pytest benchmark/tests -q``."""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.dirname(HERE), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import step_spans  # noqa: E402
from paddle_tpu.fluid import profiler  # noqa: E402


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(HERE, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def record_runs(n, phases):
    """``n`` runs in the program's ring, each with ``phases``; returns
    each phase's durations as the ring holds them."""
    for _ in range(n):
        profiler.begin_run()
        for phase in phases:
            with profiler.RecordEvent(phase):
                pass
    spans = profiler.recent_spans(last_runs=n)
    return {p: [dur for name, _, _, dur in spans if name == p]
            for p in phases}


@pytest.fixture(autouse=True)
def empty_ring():
    profiler.reset_profiler()
    yield
    profiler.reset_profiler()


TRACE = {
    "op_seconds": {"attn_block_fwd.4": 0.010, "attn_block_fwd.7": 0.014,
                   "attn_block_bwd.2": 0.050, "fusion.9": 0.5,
                   "attn_flash_bwd_dq.1": 0.006},
    "op_calls": {"attn_block_fwd.4": 60, "attn_block_fwd.7": 60,
                 "attn_block_bwd.2": 60, "fusion.9": 300,
                 "attn_flash_bwd_dq.1": 5},
}


@pytest.mark.parametrize("metric, span", [
    ("run_prepare_ms.train", profiler.SPAN_PREPARE),
    ("run_call_ms.train", profiler.SPAN_CALL),
    ("run_commit_ms.train", profiler.SPAN_COMMIT)])
def test_phase_metric_is_the_mean_over_the_windows_runs(metric, span):
    phases = (profiler.SPAN_PREPARE, profiler.SPAN_CALL,
              profiler.SPAN_COMMIT)
    record_runs(3, phases)              # set-up's runs: not the window's
    durs = record_runs(5, phases)[span]
    run = {"steps": 5, "window_s": 10.0}
    assert reader(metric)(run) == pytest.approx(1e3 * sum(durs) / 5)
    # a ring short of the window's steps, and runs that cannot all have
    # been the window's, read nothing
    assert reader(metric)({"steps": 9, "window_s": 10.0}) is None
    assert reader(metric)({"steps": 5, "window_s": 0.0}) is None


def test_mean_ms_by_hand():
    spans = [("executor.call", 7, 1.0, 0.002),
             ("executor.call", 8, 1.5, 0.004)]
    assert step_spans.mean_ms(spans, 2, 1.0) == pytest.approx(3.0)
    assert step_spans.mean_ms(spans, 3, 1.0) is None
    assert step_spans.mean_ms(spans, 2, 0.5) is None
    assert step_spans.mean_ms([], 0, 1.0) is None


def test_setup_compile_sums_the_processs_compile_spans():
    assert reader("setup_compile_s.train")({}) is None
    durs = record_runs(2, (profiler.SPAN_PREPARE, profiler.SPAN_COMPILE))
    record_runs(4, (profiler.SPAN_PREPARE, profiler.SPAN_CALL))
    assert reader("setup_compile_s.train")({}) == pytest.approx(
        sum(durs[profiler.SPAN_COMPILE]))


def test_attn_metrics_read_the_named_operations():
    run = {"trace": TRACE, "steps": 5}
    assert reader("attn_fwd_ms.train")(run) == pytest.approx(4.8)
    assert reader("attn_bwd_ms.train")(run) == pytest.approx(11.2)
    assert reader("attn_fwd_calls.train")(run) == pytest.approx(24.0)


@pytest.mark.parametrize("metric", ["attn_fwd_ms.train",
                                    "attn_bwd_ms.train",
                                    "attn_fwd_calls.train"])
def test_attn_metrics_read_nothing_without_trace_or_names(metric):
    assert reader(metric)({"trace": None, "steps": 5}) is None
    unnamed = {"op_seconds": {"jvp__.12": 0.01, "fusion.9": 0.5},
               "op_calls": {"jvp__.12": 60, "fusion.9": 300}}
    assert reader(metric)({"trace": unnamed, "steps": 5}) is None


def test_a_program_without_the_span_ring_reads_nothing(monkeypatch):
    record_runs(5, (profiler.SPAN_PREPARE, profiler.SPAN_COMPILE))
    monkeypatch.delattr(profiler, "recent_spans")
    assert reader("run_prepare_ms.train")(
        {"steps": 5, "window_s": 10.0}) is None
    assert reader("setup_compile_s.train")({}) is None
