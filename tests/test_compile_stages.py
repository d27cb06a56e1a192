"""What ``executor.compile`` is made of (``fluid/profiler.py``): JAX's own
report of each compile's stages as spans in the program's ring - inside an
``Executor.run`` under its id, outside any under run id 0 - whether the
executable came from JAX's persistent cache, what the compiled step holds
in memory, and the benchmark's readers of all of it."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor, optimizer, profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
NEW_METRICS = ("setup_trace_s.train", "setup_lower_s.train",
               "setup_backend_s.train", "setup_backend_compiles.train",
               "setup_jit_outside_s.train", "step_hbm_pct.train")


@pytest.fixture(autouse=True)
def empty_ring():
    profiler.reset_profiler()
    yield
    profiler.reset_profiler()


@pytest.fixture
def trained():
    """A small train program whose startup has run, in a scope of its own."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[16], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        loss = layers.mean(layers.fc(h, size=16))
        optimizer.Adam(1e-3).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        profiler.reset_profiler()
        yield exe, main, loss, {"x": np.ones((4, 16), np.float32)}, scope
    exe.close()


def _names(spans):
    return [s[0] for s in spans]


def test_a_first_run_leaves_its_compile_stages_under_its_run_id(trained):
    exe, main, loss, feed, _ = trained
    assert not profiler.is_profiler_enabled()
    exe.run(main, feed=feed, fetch_list=[loss])
    (compile_span,) = profiler.recent_spans(names=[profiler.SPAN_COMPILE])
    _, run_id, t0, dur = compile_span
    stages = profiler.recent_spans(names=profiler.JAX_SPANS)
    assert run_id and {s[1] for s in stages} == {run_id}
    names = set(_names(stages))
    assert {profiler.SPAN_JAX_TRACE, profiler.SPAN_JAX_LOWER} <= names
    assert len(names & {profiler.SPAN_JAX_COMPILE,
                        profiler.SPAN_JAX_CACHE_LOAD}) == 1
    # inside the compile span's interval (the listener places a stage at
    # now - its duration, which JAX took on another clock: a millisecond)
    assert all(t0 - 1e-3 <= t and t + d <= t0 + dur + 1e-3
               for _, _, t, d in stages)
    # and each name is a series of the monitor, as every phase span is
    for name in names:
        h = monitor.get_metric("profiler_event_seconds",
                               labels={"event": name})
        assert h is not None and h.count >= 1


def test_a_second_run_of_the_program_leaves_no_compile_stage(trained):
    exe, main, loss, feed, _ = trained
    exe.run(main, feed=feed, fetch_list=[loss])
    profiler.reset_profiler()
    exe.run(main, feed=feed, fetch_list=[loss])
    assert profiler.recent_spans()      # the run's own phases are there
    assert not profiler.recent_spans(names=profiler.JAX_SPANS)


def test_a_jit_outside_any_run_records_under_run_id_0(trained):
    exe, main, loss, feed, _ = trained
    exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    profiler.reset_profiler()
    jax.jit(lambda a: jnp.tanh(a) * 5 - 2)(jnp.ones(3))   # right after a run
    stages = profiler.recent_spans(names=profiler.JAX_SPANS)
    assert {profiler.SPAN_JAX_TRACE, profiler.SPAN_JAX_LOWER} <= set(
        _names(stages))
    assert {s[1] for s in stages} == {0}


def _fresh_jit():
    """The same program under a new function object: JAX's in-memory
    caches miss, its persistent cache's key is the same."""
    return jax.jit(lambda a: jnp.tanh(a) * 3 + a.sum())


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in ``tmp_path``, every compile
    kept; off again afterwards (``tests/`` run without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, k) for k in keys]
    for k, v in zip(keys, (str(tmp_path), 0, -1)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in zip(keys, old):
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("cached", [False, True])
def test_a_persistent_cache_hit_is_a_cache_load_not_a_compile(
        persistent_cache, cached):
    x = jnp.ones(7)     # made before: its own compiles are not the subject
    if cached:
        _fresh_jit()(x)     # the cache's first sight of the program
    profiler.reset_profiler()
    _fresh_jit()(x)
    names = _names(profiler.recent_spans(names=profiler.JAX_SPANS))
    loads = names.count(profiler.SPAN_JAX_CACHE_LOAD)
    compiles = names.count(profiler.SPAN_JAX_COMPILE)
    assert (loads, compiles) == ((1, 0) if cached else (0, 1))


@pytest.mark.parametrize("hit", [False, True])
def test_the_listener_reads_jaxs_own_events(hit):
    """The same through JAX's events alone, as ``compiler.py`` sends
    them: a hit is reported INSIDE the backend compile's interval."""
    if hit:
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs(BACKEND_EVENT, 0.25,
                                              fun_name="f")
    jax.monitoring.record_event_duration_secs(BACKEND_EVENT, 0.5,
                                              fun_name="g")
    jax.monitoring.record_event_duration_secs("/jax/other/duration", 9.0)
    spans = profiler.recent_spans()
    first = profiler.SPAN_JAX_CACHE_LOAD if hit else profiler.SPAN_JAX_COMPILE
    # the hit belongs to the one compile it came in; the next is a compile
    assert [(s[0], s[3]) for s in spans] == [
        (first, 0.25), (profiler.SPAN_JAX_COMPILE, 0.5)]
    assert all(s[1] == 0 for s in spans)
    now = profiler.now()
    assert all(now - d - 0.1 <= t <= now - d for _, _, t, d in spans)


def test_an_inner_jit_traced_inside_an_outer_one_counts_once():
    inner = jax.jit(lambda a: jnp.sin(a) * 2)

    @jax.jit
    def outer(a):
        return inner(a) + inner(a + 1).sum()

    outer(jnp.ones(5))
    traces = profiler.recent_spans(names=[profiler.SPAN_JAX_TRACE])
    outermost = max(traces, key=lambda s: s[3])
    nested = [s for s in traces if s is not outermost
              and outermost[2] <= s[2]
              and s[2] + s[3] <= outermost[2] + outermost[3] + 1e-4]
    assert nested, "the inner function's trace reports inside the outer's"
    covered = profiler.union_seconds(
        (t, t + d) for _, _, t, d in [outermost] + nested)
    assert covered == pytest.approx(outermost[3], abs=2e-4)
    assert covered < sum(s[3] for s in [outermost] + nested)


@pytest.mark.parametrize("intervals, seconds", [
    ([], 0.0),
    ([(0.0, 4.0), (1.0, 2.0), (1.5, 3.0)], 4.0),      # nested: once
    ([(5.0, 6.0), (0.0, 1.0), (0.5, 2.0)], 3.0),      # overlap, then a gap
    ([(0, 4000), (3000, 6000), (7000, 8000)], 7000),  # a device's, in ns
])
def test_union_seconds(intervals, seconds):
    assert profiler.union_seconds(iter(intervals)) == pytest.approx(seconds)


class _CountedLowering:
    """A jitted function that counts how often it is lowered."""

    def __init__(self, fn):
        self.fn, self.lowered = fn, 0

    def lower(self, *args):
        self.lowered += 1
        return self.fn.lower(*args)


def _nbytes(tree):
    return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def test_newest_step_memory_is_the_compilers_sizing_from_one_lowering(
        trained, monkeypatch):
    exe, main, loss, feed, _ = trained
    exe.run(main, feed=feed, fetch_list=[loss])
    fn, specs = profiler._NEWEST_STEP
    counted = _CountedLowering(fn)
    monitor.reset()
    profiler.note_compiled_step(counted, specs)
    memory = profiler.newest_step_memory()
    assert set(memory) == set(profiler.STEP_BYTES_KINDS)
    # the arguments are the state, the feeds and the rng, as noted
    assert memory["argument"] == _nbytes(specs)
    assert 0 < memory["alias"] <= memory["argument"]    # the donated state
    assert memory["temp"] >= 0
    assert memory["total"] == (
        memory["argument"] + memory["output"] - memory["alias"]
        + memory["temp"] + memory["generated_code"])
    # one lowering serves the numbers, the region table and the text
    regions = profiler.newest_step_regions()
    assert ("optimizer", "adam") in set(regions.values())
    assert profiler.newest_step_memory() is memory
    assert profiler.step_lowering(counted, specs)[0].startswith("HloModule")
    assert counted.lowered == 1
    for kind, n in memory.items():
        assert monitor.get_metric("executor_step_bytes",
                                  labels={"kind": kind}).value == n
    carried = {m["labels"]["kind"]: m["value"]
               for m in monitor.dump_json()["executor_step_bytes"]}
    assert carried == memory
    # a step compiled after it is sized afresh; one that cannot be
    # lowered again has no numbers, as it has no table
    profiler.note_compiled_step(object(), ())
    assert profiler.newest_step_memory() is None
    assert profiler.newest_step_regions() is None


def test_a_compiled_steps_hlo_text_shares_the_newest_steps_lowering(trained):
    exe, main, loss, feed, _ = trained
    exe.run(main, feed=feed, fetch_list=[loss])
    (step,) = [s for s in exe._cache.values()
               if s.fetch_names == [loss.name]]
    counted = _CountedLowering(step.fn)
    step.fn = counted
    profiler.note_compiled_step(counted, step.arg_specs)
    text = step.hlo_text()
    assert profiler.newest_step_regions() and profiler.newest_step_memory()
    assert step.hlo_text() is text and counted.lowered == 1


# -- the benchmark's readers ---------------------------------------------------
def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def _span(name, run_id, t_start, dur):
    profiler._spans.append((name, run_id, t_start, dur))


def _setup_then_window(cold):
    """A ring as a run of a cell leaves it: a jitted helper outside any
    run, a compiling run whose trace holds a nested one, a run that only
    calls, a window of 3 runs, then the reference's compile. Returns the
    ``run`` the readers take."""
    backend = profiler.SPAN_JAX_COMPILE if cold else \
        profiler.SPAN_JAX_CACHE_LOAD
    _span(profiler.SPAN_JAX_TRACE, 0, 1.0, 0.5)     # weights from the seed
    _span(profiler.SPAN_JAX_LOWER, 0, 1.5, 0.25)
    _span(backend, 0, 1.75, 1.0)
    _span(profiler.SPAN_PREPARE, 7, 9.5, 0.5)
    _span(profiler.SPAN_JAX_TRACE, 7, 10.5, 0.25)   # a kernel's inner jit
    _span(profiler.SPAN_JAX_TRACE, 7, 10.0, 2.0)    # ... inside the step's
    _span(profiler.SPAN_JAX_LOWER, 7, 12.0, 1.0)
    _span(backend, 7, 13.0, 4.0)
    _span(profiler.SPAN_COMPILE, 7, 10.0, 8.0)      # 1 s more: the remainder
    _span(profiler.SPAN_JAX_TRACE, 8, 18.5, 0.125)  # in a run's prepare, not
    _span(profiler.SPAN_PREPARE, 8, 18.5, 0.25)     # in a compile span
    _span(profiler.SPAN_CALL, 8, 18.75, 0.25)
    for i, run_id in enumerate((9, 10, 11)):
        _span(profiler.SPAN_PREPARE, run_id, 20.0 + i, 0.5)
        _span(profiler.SPAN_CALL, run_id, 20.5 + i, 0.5)
    _span(profiler.SPAN_JAX_TRACE, 0, 30.0, 5.0)    # the reference, after
    _span(profiler.SPAN_JAX_COMPILE, 0, 35.0, 50.0)
    return {"steps": 3, "window_s": 3.0,
            "memory": {"bytes_limit": 16_000, "peak_bytes_in_use": 4_000}}


@pytest.mark.parametrize("cold", [False, True])
@pytest.mark.parametrize("metric, value", [
    ("setup_trace_s.train", 2.0),           # the nested trace once
    ("setup_lower_s.train", 1.0),
    ("setup_backend_s.train", 4.0),
    ("setup_jit_outside_s.train", 1.75),    # the helper's three stages
    ("setup_backend_compiles.train", None),
])
def test_setup_readers_read_the_ring_before_the_window(metric, value, cold):
    run = _setup_then_window(cold)
    if value is None:   # the helper's and the step's, or none: both loaded
        value = 2 if cold else 0
    got = _reader(metric)(run)
    assert got == pytest.approx(value) and got is not None


def test_step_hbm_pct_is_the_compilers_total_over_the_limit(monkeypatch):
    monkeypatch.setattr(profiler, "newest_step_memory",
                        lambda: {"total": 12_000})
    run = {"memory": {"bytes_limit": 16_000, "peak_bytes_in_use": 4_000}}
    assert _reader("step_hbm_pct.train")(run) == pytest.approx(75.0)
    assert _reader("step_hbm_pct.train")({"memory": {}}) is None   # the CPU
    monkeypatch.setattr(profiler, "newest_step_memory", lambda: None)
    assert _reader("step_hbm_pct.train")(run) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_read_nothing_from_a_program_without_the_spans(
        metric, monkeypatch):
    # the parent's ring: Executor.run's phases, no ``jax.*`` span
    for run_id in (1, 2, 3):
        _span(profiler.SPAN_PREPARE, run_id, float(run_id), 0.5)
        _span(profiler.SPAN_COMPILE if run_id == 1 else profiler.SPAN_CALL,
              run_id, run_id + 0.5, 0.25)
    run = {"steps": 2, "window_s": 2.0,
           "memory": {"bytes_limit": 16_000, "peak_bytes_in_use": 4_000}}
    monkeypatch.setattr(profiler, "_NEWEST_STEP", None)
    assert _reader(metric)(run) is None     # names known, none recorded
    monkeypatch.delattr(profiler, "JAX_SPANS")
    monkeypatch.delattr(profiler, "newest_step_memory")
    assert _reader(metric)(run) is None     # the parent's profiler
