"""The training step's spans and names (``fluid/profiler.py``): the
``Executor.run`` phases as ``TraceAnnotation``s in any ``jax.profiler``
trace and in the always-on ring, a name on every Pallas kernel and lowered
op, and the device table that reads the names back."""

import ast
import glob
import os
import types

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import telemetry
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.fluid import layers, monitor, optimizer, profiler
from paddle_tpu.kernels import attention

PHASES = (profiler.SPAN_PREPARE, profiler.SPAN_COMPILE, profiler.SPAN_CALL,
          profiler.SPAN_COMMIT, profiler.SPAN_FETCH)


def _train_program(width=32, depth=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[width], dtype="float32")
        h = x
        for i in range(depth):
            h = layers.fc(h, size=width, act="relu", name="layer_%d_ffn" % i)
        loss = layers.mean(h)
        optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss, {"x": np.ones((8, width), np.float32)}


@pytest.fixture
def trained(request):
    """A train program whose startup has run, in a scope of its own."""
    main, startup, loss, feed = _train_program(
        depth=getattr(request, "param", 3))
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        yield exe, main, loss, feed


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    plane = ProfileData.from_file(path).find_plane_with_name(
        profiler.HOST_PLANE)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in plane.lines for e in line.events]


def _start_trace(trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def test_phases_are_in_the_host_plane_of_any_jax_trace(trained, tmp_path):
    exe, main, loss, feed = trained
    _start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer_window"):
            for _ in range(2):
                exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    (outer,) = [e for e in events if e[0] == "outer_window"]
    ours = [e for e in events if e[0] in PHASES]
    assert [e[0] for e in sorted(ours, key=lambda e: e[1])] == [
        profiler.SPAN_PREPARE, profiler.SPAN_COMPILE, profiler.SPAN_COMMIT,
        profiler.SPAN_FETCH,
        profiler.SPAN_PREPARE, profiler.SPAN_CALL, profiler.SPAN_COMMIT,
        profiler.SPAN_FETCH]
    assert all(outer[1] <= a and b <= outer[2] for _, a, b in ours)


# sixteen layers: a state of a hundred arrays, so that the fixed cost
# between the spans is small beside them, as in a model's step
@pytest.mark.parametrize("trained", [16], indirect=True)
def test_ring_holds_the_last_runs_with_the_profiler_never_started(trained):
    exe, main, loss, feed = trained
    assert not profiler.is_profiler_enabled()
    walls = []
    hook = executor_mod.register_run_hook(
        lambda record: walls.append(record["wall_time"]))
    try:
        exe.run(main, feed=feed, fetch_list=[loss])     # the compile
        for _ in range(20):
            exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    finally:
        executor_mod.unregister_run_hook(hook)
    spans = profiler.recent_spans(last_runs=20)
    by_run = {}
    for name, run_id, _, dur in spans:
        by_run.setdefault(run_id, {})[name] = dur
    assert len(by_run) == 20 and 0 not in by_run
    assert all(set(phases) == {profiler.SPAN_PREPARE, profiler.SPAN_CALL,
                               profiler.SPAN_COMMIT}
               for phases in by_run.values())
    # the three phases are the run: what the wall clock of the same runs
    # (executor_run_seconds observes it) holds beside them is the lookup
    share = sum(dur for _, _, _, dur in spans) / sum(walls[-20:])
    assert 0.8 <= share <= 1.0, share
    # and the compile before them is in the ring under its own name
    assert profiler.recent_spans(names=[profiler.SPAN_COMPILE])


def test_recent_spans_keeps_names_and_the_newest_runs():
    profiler.reset_profiler()
    for _ in range(3):
        profiler.begin_run()
        for name in (profiler.SPAN_PREPARE, profiler.SPAN_CALL):
            with profiler.RecordEvent(name):
                pass
    with profiler.RecordEvent("not_a_phase"):    # the profiler is off
        pass
    spans = profiler.recent_spans()
    assert [s[0] for s in spans] == [profiler.SPAN_PREPARE,
                                     profiler.SPAN_CALL] * 3
    run_ids = sorted({s[1] for s in spans})
    assert len(run_ids) == 3
    newest = profiler.recent_spans(names=[profiler.SPAN_CALL], last_runs=2)
    assert [(s[0], s[1]) for s in newest] == [
        (profiler.SPAN_CALL, run_ids[1]), (profiler.SPAN_CALL, run_ids[2])]
    assert all(t > 0 and dur >= 0 for _, _, t, dur in spans)


def test_iters_k_records_the_same_names_under_one_run_id(trained):
    exe, main, loss, feed = trained
    stacked = {"x": np.stack([feed["x"]] * 4)}
    for _ in range(2):
        exe.run(main, feed=stacked, fetch_list=[loss], iters=4)
    first, second = {}, {}
    spans = profiler.recent_spans(last_runs=2)
    ids = sorted({s[1] for s in spans})
    for name, run_id, _, _ in spans:
        (first if run_id == ids[0] else second).setdefault(name, 0)
    # (the compile's own stages, ``jax.*``: tests/test_compile_stages.py)
    assert set(first) - set(profiler.JAX_SPANS) == {
        profiler.SPAN_PREPARE, profiler.SPAN_COMPILE, profiler.SPAN_COMMIT,
        profiler.SPAN_FETCH}
    assert set(second) == {profiler.SPAN_PREPARE, profiler.SPAN_CALL,
                           profiler.SPAN_COMMIT, profiler.SPAN_FETCH}


def test_no_phase_span_waits_for_the_device(trained, monkeypatch):
    exe, main, loss, feed = trained

    def refuse(*a, **kw):
        raise AssertionError("a phase span waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    (lv,) = exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    (run,) = {s[1] for s in profiler.recent_spans(last_runs=1)}
    names = {s[0] for s in profiler.recent_spans(last_runs=1)}
    assert run and names - set(profiler.JAX_SPANS) == {
        profiler.SPAN_PREPARE, profiler.SPAN_COMPILE, profiler.SPAN_COMMIT}
    assert np.isfinite(np.asarray(lv)).all()


def test_a_tracing_profiler_does_not_block_and_run_events_are_no_series(
        trained, monkeypatch, tmp_path):
    exe, main, loss, feed = trained
    monitor.reset()
    profiler.reset_profiler()
    profiler.start_profiler()
    exe.run(main, feed=feed, fetch_list=[loss])     # waits: the table's time
    profiler.stop_profiler(silent=True)
    (event,) = [n for n in profiler._events if n.startswith("executor_run[")]
    assert "#p%d" % main._uid in event
    assert monitor.get_metric("profiler_event_seconds",
                              labels={"event": event}) is None
    h = monitor.get_metric("profiler_event_seconds",
                           labels={"event": profiler.SPAN_PREPARE})
    assert h is not None and h.count == 1

    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(1) or real(x))
    profiler.reset_profiler()
    profiler.start_profiler(trace_dir=str(tmp_path))
    try:
        exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    finally:
        report = profiler.stop_profiler(silent=True)
    assert not blocked
    assert event in report and "Device time by region" in report


def _calls(module, func_name):
    """The ``ast.Call`` nodes of ``module``'s source that call
    ``func_name`` (a bare name or an attribute)."""
    found = []
    for node in ast.walk(ast.parse(open(module.__file__).read())):
        if isinstance(node, ast.Call):
            f = node.func
            if getattr(f, "attr", getattr(f, "id", None)) == func_name:
                found.append(node)
    return found


def test_every_pallas_call_carries_a_name_of_the_table():
    from paddle_tpu.kernels import causal_conv, common, delta_rule

    # the package's one raw call is the shared helper's, and it passes the
    # name on; neither kernel file makes one of its own
    (call,) = _calls(common, "pallas_call")
    assert any(kw.arg == "name" and isinstance(kw.value, ast.Name)
               and kw.value.id == "name" for kw in call.keywords)
    assert not _calls(attention, "pallas_call")
    assert not _calls(delta_rule, "pallas_call")
    assert not _calls(causal_conv, "pallas_call")
    (lifted,) = _calls(attention, "named_pallas_call")  # in _kernel_call
    assert isinstance(lifted.args[0], ast.Name)
    named = [c.args[0] for c in _calls(attention, "_kernel_call")]
    assert all(isinstance(a, ast.Constant) for a in named)
    names = [a.value for a in named]
    assert len(names) == 17 and len(set(names)) == len(names)
    assert set(names) == set(attention.KERNEL_NAMES)
    for tier in attention.KERNEL_TIERS:     # a name for every counted tier
        stem = "attn_" + tier.replace("_bwd", "")
        assert any(n.startswith(stem) for n in names), tier
    # the delta rule's kernels: named, and never under the attention
    # metrics' prefix
    # (a site names its kernel by the rule: ``"kda_x" if channel else
    # "gdn_x"`` - the two branches are read, never the condition)
    gdn = [c.args[0] for c in _calls(delta_rule, "named_pallas_call")]
    pairs = []
    for a in gdn:
        assert isinstance(a, ast.IfExp), ast.dump(a)
        assert all(isinstance(n, ast.Constant) and isinstance(n.value, str)
                   for n in (a.body, a.orelse)), ast.dump(a)
        pairs.append((a.body.value, a.orelse.value))
    for kda, scalar in pairs:       # one stem a site, a name for each rule
        assert kda.startswith("kda_") and scalar == "gdn_" + kda[4:]
    assert sorted(n for p in pairs for n in p) == sorted(
        delta_rule.KERNEL_NAMES)
    assert not set(delta_rule.KERNEL_NAMES) & set(attention.KERNEL_NAMES)
    # the short convolution's: constants, a name each, shared with neither
    conv = [c.args[0] for c in _calls(causal_conv, "named_pallas_call")]
    assert all(isinstance(a, ast.Constant) for a in conv)
    assert sorted(a.value for a in conv) == sorted(causal_conv.KERNEL_NAMES)
    assert not set(causal_conv.KERNEL_NAMES) & (
        set(attention.KERNEL_NAMES) | set(delta_rule.KERNEL_NAMES))
    assert not any(n.startswith(("attn_", "gdn_", "kda_"))
                   for n in causal_conv.KERNEL_NAMES)


def test_lowered_text_names_the_program_op_of_every_operation(trained):
    exe, main, loss, feed = trained
    exe.run(main, feed=feed, fetch_list=[loss])
    (step,) = [s for s in exe._cache.values()
               if s.fetch_names == [loss.name]]
    assert step.fn.__name__ == "train_step"
    _, args = exe.as_function(main, feed, [loss])
    text = step.fn.lower(*args).as_text(debug_info=True)
    assert "autodiff/jvp(layer_1_mul)/" in text     # forward: the replay's
    assert "jit(train_step)/layer_1_mul/" not in text     # and only the one
    assert "autodiff/transpose(jvp(layer_1_mul))/" in text        # backward
    assert "jit(train_step)/layer_1_adam/" in text                # optimizer


@pytest.mark.parametrize("op_name, region", [
    ("jit(train_step)/layer_3_mul/dot_general", ("forward", "mul")),
    ("jit(train_step)/autodiff/jvp(layer_norm)/jit(_var)/mul",
     ("forward", "layer_norm")),
    ("jit(train_step)/autodiff/transpose(jvp(layer_0_mul))/dot_general",
     ("backward", "mul")),
    ("jit(train_step)/autodiff/transpose(autodiff)/"
     "jvp(fused_multihead_attention)/attn_block_bwd/attn_block_bwd/"
     "pallas_call", ("backward", "fused_multihead_attention")),
    ("jit(train_step)/autodiff/add_any", ("backward", "autodiff")),
    ("jit(train_step)/layer_0_adam/mul", ("optimizer", "adam")),
    ("jit(train_step)/mul", ("unattributed", "")),   # a primitive, no scope
    ("", ("unattributed", "")),
])
def test_region_of_an_op_name(op_name, region):
    assert profiler.region_of(op_name) == region


HLO_TEXT = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %add.5 = f32[8]{0} add(%p, %p), metadata={op_name="jit(train_step)/layer_0_mul/add" stack_frame_id=4}
}

ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/layer_0_mul/dot_general" stack_frame_id=4}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(train_step)/autodiff/transpose(jvp(layer_0_mul))/dot_general"}
  %select.3 = f32[8]{0} select(%fusion.2), metadata={op_name="jit(train_step)/autodiff/transpose(jvp(relu))/select_n"}
  %sqrt.4 = f32[8]{0} sqrt(%select.3), metadata={op_name="jit(train_step)/adam/sqrt"}
  ROOT %copy.3 = f32[8]{0} copy(%sqrt.4)
}
"""


def test_device_table_parses_a_profile_into_the_phases():
    def event(name, start, dur):
        return types.SimpleNamespace(
            name=name, start_ns=start, duration_ns=dur,
            stats=[("device_offset_ps", start * 1000)])

    ops = [event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop",
                 0, 4000),
           event("%fusion.2 = f32[8]{0} fusion(%fusion.1)", 3000, 3000),
           event("%select.3 = f32[8]{0} select(%fusion.2)", 7000, 1000),
           event("%sqrt.4 = f32[8]{0} sqrt(%select.3)", 9000, 2000),
           event("%copy.3 = f32[8]{0} copy(%sqrt.4)", 12000, 500),
           # another module's operation of a name this one has too
           event("%fusion.1 = f32[2]{0} fusion()", 20000, 700)]
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="Steps", events=[event("7", 0, 10 ** 9)]),
        types.SimpleNamespace(name=profiler.DEVICE_MODULE_LINE, events=[
            event("jit_train_step(11881051374078078384)", 0, 13000),
            event("jit_step(42)", 19000, 2000)]),
        types.SimpleNamespace(name=profiler.DEVICE_OP_LINE, events=ops)])
    host = types.SimpleNamespace(name=profiler.HOST_PLANE, lines=[])
    profile = types.SimpleNamespace(planes=[host, device])
    assert profiler.op_names_of(HLO_TEXT)["add.5"].endswith("layer_0_mul/add")
    regions = profiler.device_time_by_region(
        profile, {"jit_train_step": HLO_TEXT})
    assert regions["phases"] == {
        "forward": [1, pytest.approx(4e-6)],
        "backward": [2, pytest.approx(4e-6)],
        "optimizer": [1, pytest.approx(2e-6)],
        "unattributed": [2, pytest.approx(1.2e-6)]}
    assert regions["ops"][("backward", "mul")] == [1, pytest.approx(3e-6)]
    assert regions["busy_s"] == pytest.approx(10.2e-6)  # the overlap once
    report = profiler.region_report(regions)
    rows = {l.split()[0]: l for l in report.splitlines() if l.strip()}
    assert rows["forward"].split()[-1] == "35.71%"      # 4000 of 11200
    assert "backward mul" in report and "optimizer adam" in report
    # the trace's own name, and what it is made of
    assert regions["instructions"][("fusion", "backward", "mul")] == [
        1, pytest.approx(3e-6)]
    fusion = report.splitlines().index(rows["fusion"])
    assert report.splitlines()[fusion + 1].split()[:2] == ["forward", "mul"]
    # with no module's text every operation is unattributed; with no
    # device plane there is nothing to report
    bare = profiler.device_time_by_region(profile)
    assert bare["phases"]["unattributed"] == [6, pytest.approx(11.2e-6)]
    assert "no device operation" in profiler.region_report(
        profiler.device_time_by_region(types.SimpleNamespace(planes=[host])))


def test_a_compiled_step_gives_its_hlo_text_with_op_names(trained):
    exe, main, loss, feed = trained
    exe.run(main, feed=feed, fetch_list=[loss])
    (step,) = [s for s in exe._cache.values()
               if s.fetch_names == [loss.name]]
    assert step in executor_mod.compiled_steps()
    names = profiler.op_names_of(step.hlo_text())
    regions = {profiler.region_of(n) for n in names.values()}
    assert {("forward", "mul"), ("backward", "mul"),
            ("optimizer", "adam")} <= regions
    exe.close()


def test_telemetry_span_and_record_event_land_in_one_trace(tmp_path):
    _start_trace(str(tmp_path))
    try:
        with telemetry.span("request.decode"):
            with profiler.RecordEvent("user_section"):
                pass
    finally:
        jax.profiler.stop_trace()
    spans = {e[0]: e for e in _host_events(str(tmp_path))}
    assert {"request.decode", "user_section"} <= set(spans)
    outer, inner = spans["request.decode"], spans["user_section"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
