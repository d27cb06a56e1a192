"""Profiler — reference ``python/paddle/fluid/profiler.py:228`` +
``platform/profiler.h:81,166`` (RecordEvent, Enable/DisableProfiler,
per-event summary table, chrome timeline via ``tools/timeline.py``).

TPU-native: under XLA the per-op host interpreter is gone, so host-side
events are step/section-level. ``RecordEvent`` is the program's one host
span: it always enters a ``jax.profiler.TraceAnnotation`` of its name, so
whoever takes a device trace (``start_profiler(trace_dir=...)``, a
benchmark driver, an operator's ``jax.profiler.start_trace``) finds the
program's spans in the ``/host:CPU`` plane of the same ``.xplane.pb``, on
the clock of the device's ``XLA Ops`` line. The spans of ``PHASE_SPANS``
(where ``Executor.run`` does its work, and the stages of every compile JAX
makes in the process, as ``jax.monitoring`` reports them) are also kept in
a ring whether or not the profiler was started: the last seconds of any
run can be read back with ``recent_spans``. The summary table keeps the
reference's shape (Event / Calls / Total / Min / Max / Ave / Ratio); after
a device trace ``stop_profiler`` appends device time by region, read from
the names ``registry.lower_op`` and the Pallas kernels put on the device's
work.
"""

import contextlib
import glob
import itertools
import os
import re
import threading
import time
from collections import OrderedDict, deque

from jax import monitoring as _jax_monitoring
from jax.profiler import TraceAnnotation

from . import monitor as _monitor

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "export_chrome_tracing", "dropped_span_count", "recent_spans",
           "union_seconds", "device_time_by_region", "newest_step_regions",
           "newest_step_memory", "RecordEvent", "PHASE_SPANS", "JAX_SPANS",
           "cuda_profiler", "npu_profiler"]

# -- the program's span names: the one table ---------------------------------
# Executor.run, in the order a run passes through them (README
# "Observability" says what each covers)
SPAN_PREPARE = "executor.prepare"
SPAN_COMPILE = "executor.compile"     # an in-memory miss, in place of call
SPAN_CALL = "executor.call"
SPAN_COMMIT = "executor.commit"
SPAN_FETCH = "executor.fetch"         # the one phase that waits for the device
# inside executor.compile, where the disk tier is on (compile_cache.wrap_jit)
SPAN_CACHE_LOAD = "compile_cache.load"
SPAN_CACHE_COMPILE = "compile_cache.compile"
SPAN_CACHE_SAVE = "compile_cache.save"
# the stages of a compile as JAX itself reports them (``jax.monitoring``),
# for EVERY jitted function of the process: inside executor.compile under
# that run's id, under run id 0 anywhere else (a driver's jitted helpers,
# the per-op compiles of a dygraph eager trace). A function traced inside
# another reports its own span inside the outer one's: read a name's time
# with ``union_seconds``, never as the plain sum.
SPAN_JAX_TRACE = "jax.trace"          # Python's walk of the ops into a jaxpr
SPAN_JAX_LOWER = "jax.lower"          # jaxpr to StableHLO, Mosaic's inside
SPAN_JAX_COMPILE = "jax.backend_compile"    # XLA's compile: no cache had it
SPAN_JAX_CACHE_LOAD = "jax.cache_load"      # read from the persistent cache
JAX_SPANS = (SPAN_JAX_TRACE, SPAN_JAX_LOWER, SPAN_JAX_COMPILE,
             SPAN_JAX_CACHE_LOAD)
# always in the ring and in profiler_event_seconds: a fixed, small set
PHASE_SPANS = frozenset({
    SPAN_PREPARE, SPAN_COMPILE, SPAN_CALL, SPAN_COMMIT, SPAN_FETCH,
    SPAN_CACHE_LOAD, SPAN_CACHE_COMPILE, SPAN_CACHE_SAVE, *JAX_SPANS})

_enabled = False
_events = OrderedDict()  # name -> [calls, total, min, max]
_trace_dir = None
_MAX_SPANS = 200_000
# (name, run_id, t_start, dur) ring, times on perf_counter. A RING, not a
# capped list: on overflow the OLDEST span is evicted, so the buffer
# always holds the last seconds of the run — the flight recorder's
# postmortem window — instead of the first seconds of warm-up.
_spans = deque(maxlen=_MAX_SPANS)
_dropped = [0]           # spans evicted past _MAX_SPANS
# the sequence number of the Executor.run call a thread is in: the spans
# of one run share it (0: recorded outside any run)
_run_ids = itertools.count(1)
_current_run = threading.local()

_M_DROPPED = _monitor.counter(
    "profiler_dropped_spans_total",
    help="host spans evicted from the full span ring (oldest-out; the "
         "ring keeps the newest _MAX_SPANS)")
# one monitor histogram series per event name, cached so the per-record
# cost is a dict hit rather than a registry lookup
_mon_hists = {}


def _mon_hist(name):
    h = _mon_hists.get(name)
    if h is None:
        h = _monitor.histogram(
            "profiler_event_seconds",
            help="host RecordEvent/Executor span durations",
            labels={"event": name})
        _mon_hists[name] = h
    return h


def now():
    return time.perf_counter()


def dropped_span_count():
    """Spans evicted since the last reset_profiler() (ring overflow —
    the evicted spans are the OLDEST; the ring keeps the newest)."""
    return _dropped[0]


def begin_run():
    """A new run id for the ``Executor.run`` call this thread enters; the
    phase spans the thread records until ``end_run`` carry it."""
    _current_run.run_id = run_id = next(_run_ids)
    return run_id


def end_run():
    """The thread's ``Executor.run`` call is over: what it records from
    here on (a later ``FetchHandle.numpy()``, a compile of the caller's
    own) is outside any run, under run id 0."""
    _current_run.run_id = 0


def _record(name, seconds, series=True):
    """One finished span: into the summary table while the profiler is
    on, into the ring and ``profiler_event_seconds`` while it is on or
    the name is one of ``PHASE_SPANS``. ``series=False`` keeps a name
    that is not from a fixed set (``executor_run[...#p<uid>]``) out of
    the monitor's label space."""
    phase = name in PHASE_SPANS
    if _enabled:
        e = _events.get(name)
        if e is None:
            _events[name] = [1, seconds, seconds, seconds]
        else:
            e[0] += 1
            e[1] += seconds
            e[2] = min(e[2], seconds)
            e[3] = max(e[3], seconds)
    elif not phase:
        return
    if series:
        _mon_hist(name).observe(seconds)
    if len(_spans) == _spans.maxlen:   # appending evicts the oldest
        _dropped[0] += 1
        _M_DROPPED.inc()
    _spans.append((name,
                   getattr(_current_run, "run_id", 0) if phase else 0,
                   time.perf_counter() - seconds, seconds))


class RecordEvent:
    """RAII host event (reference platform/profiler.h:81), and a
    ``TraceAnnotation`` of the same name: in any ``jax.profiler`` trace
    being taken, on the device trace's clock; nothing when none is."""

    __slots__ = ("name", "_t0", "_annotation")

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _record(self.name, seconds)
        return False


def record_event(name):
    return RecordEvent(name)


# -- JAX's own report of a compile's stages ------------------------------------
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": SPAN_JAX_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": SPAN_JAX_LOWER,
    "/jax/core/compile/backend_compile_duration": SPAN_JAX_COMPILE,
}
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# whether the backend compile this thread is in found its executable in
# JAX's persistent cache: JAX reports the hit inside the compile's interval
# (``compiler.compile_or_get_cached``) and the interval when it closes
_jax_cache_hit = threading.local()


def _on_jax_event(event, **kwargs):
    if event == _JAX_CACHE_HIT:
        _jax_cache_hit.seen = True


def _on_jax_duration(event, duration_secs, **kwargs):
    """A finished stage of a compile, as a span that ends now. JAX calls
    this where it traces, lowers or compiles: never on a cached call."""
    name = _JAX_STAGES.get(event)
    if name is None:
        return
    if name == SPAN_JAX_COMPILE and getattr(_jax_cache_hit, "seen", False):
        _jax_cache_hit.seen = False
        name = SPAN_JAX_CACHE_LOAD
    _record(name, duration_secs)


_jax_monitoring.register_event_listener(_on_jax_event)
_jax_monitoring.register_event_duration_secs_listener(_on_jax_duration)


def union_seconds(intervals):
    """What ``(start, end)`` intervals cover, overlaps counted once: the
    time of spans that nest (a ``jax.trace`` inside another's) or run
    side by side (a device's operations)."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        total += max(b, end) - max(a, end)
        end = max(b, end)
    return total


def recent_spans(names=None, last_runs=None):
    """The ring's spans, oldest first, as ``(name, run_id, t_start,
    dur)`` on ``perf_counter``. ``names`` keeps those names only;
    ``last_runs=n`` keeps the spans of the newest ``n`` ``Executor.run``
    calls in the ring (spans recorded outside a run have no run id and
    are left out)."""
    spans = list(_spans)
    if last_runs is not None:
        runs, first = set(), len(spans)
        for i in range(len(spans) - 1, -1, -1):
            run_id = spans[i][1]
            if run_id and run_id not in runs:
                if len(runs) == last_runs:
                    break
                runs.add(run_id)
            first = i
        spans = [s for s in spans[first:] if s[1] in runs]
    if names is not None:
        names = frozenset(names)
        spans = [s for s in spans if s[0] in names]
    return spans


def is_profiler_enabled():
    return _enabled


def times_runs():
    """Whether ``Executor.run`` should wait for its step so that the
    table's ``executor_run[...]`` event is the step's time: while the
    profiler is on and is not itself taking a device trace (the wait
    would serialise the pipeline the trace is there to show)."""
    return _enabled and _trace_dir is None


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    """Enable host-event collection; with ``trace_dir`` also start a
    jax.profiler device trace (the CUPTI/DeviceTracer analogue)."""
    global _enabled, _trace_dir
    _enabled = True
    _trace_dir = trace_dir
    if trace_dir is not None:
        import jax

        # the program's spans are TraceAnnotations: with Python's own
        # tracer on, every call of the host would be an event as well
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_profiler(sorted_key=None, profile_path=None, timeline_path=None,
                  silent=False):
    """Disable collection, print the summary table (suppressed with
    ``silent`` — the dygraph gperf route wants collection without the
    stdout table), optionally write it to ``profile_path``, stop the
    device trace if one is running and append its device time by region
    to the report, and — with ``timeline_path`` — export a
    chrome://tracing JSON (the reference's ``tools/timeline.py`` output,
    host events + any captured device ops)."""
    global _enabled, _trace_dir
    _enabled = False
    trace_dir = _trace_dir
    profile = None
    if _trace_dir is not None:
        import jax

        jax.profiler.stop_trace()
        _trace_dir = None
        profile = _load_trace(trace_dir)
    report = summary(sorted_key)
    if profile is not None:
        report += "\n\n" + region_report(device_time_by_region(
            profile, _live_hlo_texts(profile)))
    if not silent:
        print(report)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(report)
    if timeline_path:
        export_chrome_tracing(timeline_path, profile=profile)
    return report


# -- reading a device trace ---------------------------------------------------
DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
DEVICE_MODULE_LINE = "XLA Modules"     # ``jit_train_step(<fingerprint>)``
# The device line names an operation by its HLO text (``%fusion.12 = ...``)
# without its metadata, so the ``op_name`` it was traced under
# (``jit(train_step)/autodiff/transpose(jvp(layer_3_mul))/dot_general``) is
# looked up by instruction name in the compiled module's own text
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([^\s=]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = (.*)$", re.M)
PHASES = ("forward", "backward", "optimizer", "unattributed")
# ``transpose(jvp(layer_3_mul))`` -> ``layer_3_mul``
_SCOPE = re.compile(r"^(?:[\w.]+\()*([^()]*)\)*$")


def _load_trace(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` as a ``ProfileData``,
    or None where the trace wrote none."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return ProfileData.from_file(files[-1]) if files else None


def _device_events(profile, line_name=DEVICE_OP_LINE):
    """``(plane, line id, event)`` of every operation a device ran (or of
    another of the device planes' lines)."""
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for i, line in enumerate(plane.lines):
                if line.name == line_name:
                    for event in line.events:
                        yield plane.name, i, event


def _instruction(event_name):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# An instruction that only holds other computations: the device line
# gives it an event that spans its body's events, which are there too.
# Counting both would count a ``lax.scan``'s or ``lax.cond``'s work twice.
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})
_OPCODE = re.compile(r"[\]\})]\s([a-z][a-z\-]*)\(")


def _is_container(hlo_line):
    """Whether an instruction's HLO text (``%while.3 = (...) while(...)``,
    as the device line names an event, or a line of a module's text) is
    of ``CONTAINER_OPCODES``."""
    found = _OPCODE.search(hlo_line.split(" = ", 1)[-1])
    return found is not None and found.group(1) in CONTAINER_OPCODES


def _module_name(event_name):
    """``jit_train_step(11881051374078078384)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


_CUSTOM_CALL_OPERANDS = re.compile(r"\bcustom-call\(([^)]*)\)")
_OPERAND = re.compile(r"%([^\s,)]+)")


def op_names_of(hlo_text):
    """``{instruction name: op_name}`` of a compiled module's HLO text.
    A kernel that the compiler put in a program op's place (the TPU's
    ``lax.ragged_dot``: a ``custom-call`` whose ``op_name`` is the
    compiler's own, ``ragged-dot-none``, with no scope in it) takes the
    ``op_name`` of its first operand that has a scope: what feeds it was
    lowered under the same program op."""
    names = dict(_HLO_OP_NAME.findall(hlo_text))
    for name, rest in _HLO_LINE.findall(hlo_text):
        if "/" in names.get(name, "/"):
            continue
        call = _CUSTOM_CALL_OPERANDS.search(rest)
        for operand in _OPERAND.findall(call.group(1)) if call else ():
            if "/" in names.get(operand, ""):
                names[name] = names[operand]
                break
    return names


# The newest compiled step, kept as what it can be lowered from again (the
# jitted function and its arguments' shapes, no array), so that a reader
# outside the program can file a device instruction under its program op,
# or ask what the step holds in memory, after the step object and its
# Executor are gone.
_NEWEST_STEP = None
_NEWEST_LOWERING = None     # (HLO text, memory) of it: ONE lowering's
_NEWEST_REGIONS = None
_NEWEST_MODULES = None
STEP_BYTES_KINDS = ("argument", "output", "alias", "temp", "generated_code",
                    "total")
_M_STEP_BYTES = {
    kind: _monitor.gauge(
        "executor_step_bytes",
        help="device memory of the newest compiled step as the compiler "
             "sized it, set where profiler.newest_step_memory() or the "
             "region table lowered it",
        labels={"kind": kind})
    for kind in STEP_BYTES_KINDS}


def note_compiled_step(fn, arg_specs):
    """``Executor.run`` calls this where it has compiled a step."""
    global _NEWEST_STEP, _NEWEST_LOWERING, _NEWEST_REGIONS, _NEWEST_MODULES
    _NEWEST_STEP = (fn, arg_specs)
    _NEWEST_LOWERING = _NEWEST_REGIONS = _NEWEST_MODULES = None


def _step_memory(compiled):
    """``compiled.memory_analysis()`` as ``{kind: bytes}`` over
    ``STEP_BYTES_KINDS``; the donated state (``alias``) is in ``argument``
    and in ``output`` and counts once in ``total``. None where the backend
    gives no analysis."""
    stats = compiled.memory_analysis()
    if stats is None:
        return None
    memory = {kind: getattr(stats, kind + "_size_in_bytes")
              for kind in STEP_BYTES_KINDS[:-1]}
    memory["total"] = (memory["argument"] + memory["output"]
                       - memory["alias"] + memory["temp"]
                       + memory["generated_code"])
    return memory


def step_lowering(fn, arg_specs):
    """``(HLO text, memory)`` of a compiled step, lowered again from its
    noted shapes (JAX's caches make that seconds): the module's text with
    each instruction's ``op_name``, and ``_step_memory`` of the compiler's
    own analysis, temporaries included. The text and the numbers are
    kept, not the ``Compiled``; the newest step's are kept here, so its
    text, region table and memory come from one lowering. None where the
    step cannot be lowered (never called, or a disk-tier or sharded
    wrapper)."""
    global _NEWEST_LOWERING
    newest = _NEWEST_STEP is not None and _NEWEST_STEP[0] is fn
    if newest and _NEWEST_LOWERING is not None:
        return _NEWEST_LOWERING
    if arg_specs is None or not hasattr(fn, "lower"):
        return None
    compiled = fn.lower(*arg_specs).compile()
    lowering = (compiled.as_text(), _step_memory(compiled))
    if newest:
        _NEWEST_LOWERING = lowering
        for kind, n in (lowering[1] or {}).items():
            _M_STEP_BYTES[kind].set(n)
    return lowering


def _newest_lowering():
    return step_lowering(*_NEWEST_STEP) if _NEWEST_STEP is not None else None


def _newest_op_names():
    """``{instruction name: op_name}`` of the newest compiled step's
    module, containers left out, or None."""
    lowering = _newest_lowering()
    if lowering is None:
        return None
    text = lowering[0]
    containers = {name for name, rest in _HLO_LINE.findall(text)
                  if _is_container(rest)}
    return {name: op_name for name, op_name in op_names_of(text).items()
            if name not in containers}


def newest_step_regions():
    """``{instruction name: (phase, program op type)}`` of the newest
    compiled step's module (``region_of`` of each instruction's
    ``op_name``; ``while`` / ``conditional`` / ``call`` instructions left
    out, their bodies' being there), built on the first call from
    ``step_lowering``. None where no step was compiled or it cannot be
    lowered (a disk-tier or sharded wrapper)."""
    global _NEWEST_REGIONS
    if _NEWEST_REGIONS is None:
        names = _newest_op_names()
        if names is not None:
            _NEWEST_REGIONS = {name: region_of(op_name)
                               for name, op_name in names.items()}
    return _NEWEST_REGIONS


def newest_step_modules():
    """``{instruction name: model module}`` of the newest compiled step's
    module (``module_of`` of each instruction's ``op_name``: ``"mtp"`` for
    what an op built inside ``fluid.name_scope("mtp")`` lowered to -
    forward, replay and backward - and ``""`` for the rest), beside
    ``newest_step_regions`` and from the same lowering. None where that
    table is."""
    global _NEWEST_MODULES
    if _NEWEST_MODULES is None:
        names = _newest_op_names()
        if names is not None:
            _NEWEST_MODULES = {name: module_of(op_name)
                               for name, op_name in names.items()}
    return _NEWEST_MODULES


def newest_step_memory():
    """What the newest compiled step holds in device memory, in bytes, as
    the compiler sized it: ``{"argument", "output", "alias", "temp",
    "generated_code", "total"}``, ``total = argument + output - alias +
    temp + generated_code``. The allocator's ``peak_bytes_in_use`` leaves
    a program's temporaries out; this has them. From the region table's
    lowering, built on the first call of either (an untraced run pays
    nothing), and then also in the gauges ``executor_step_bytes{kind}``.
    None where the region table is."""
    lowering = _newest_lowering()
    return lowering[1] if lowering is not None else None


def _live_hlo_texts(profile):
    """``{module name: HLO text}`` for the modules that ran in the trace,
    from the compiled steps the process's Executors still hold (where
    two share a module name, the first found)."""
    from . import executor

    wanted = {_module_name(e.name) for _, _, e in
              _device_events(profile, DEVICE_MODULE_LINE)}
    texts = {}
    for step in executor.compiled_steps():
        name = "jit_" + getattr(step.fn, "__name__", "")
        if name in wanted and name not in texts:
            try:
                text = step.hlo_text()
            except Exception:   # the report goes on without this module
                import logging

                logging.getLogger(__name__).exception(
                    "profiler: no HLO text for module %s; its operations "
                    "stay unattributed", name)
                continue
            if text:
                texts[name] = text
    return texts


def region_of(op_name):
    """``(phase, program op type)`` of a device operation's ``op_name``.
    The op type is the outermost scope that ``registry.lower_op`` opened
    (``registry.op_scope``, layer tag taken off), or under ``autodiff``
    the replayed forward op's; the last component is the JAX primitive
    and no scope. Backward is what ran under ``transpose(`` or as the
    ``autodiff`` op's own work, optimizer what an op of
    ``ops/optimizer_ops.py`` lowered. An operation XLA inserted carries
    no ``op_name`` and is unattributed."""
    from .registry import LAYER_TAG, registry

    op_type = None
    for scope in (op_name or "").split("/")[:-1]:
        m = None if scope.startswith(("jit(", "pjit(")) \
            else _SCOPE.match(scope)
        inner = LAYER_TAG.sub("", m.group(1)) if m else ""
        if registry.has(inner):
            op_type = inner
            if inner != "autodiff":
                break
    if op_type is None:
        return "unattributed", ""
    if registry.get(op_type).lower.__module__.endswith(".optimizer_ops"):
        return "optimizer", op_type
    if "transpose(" in op_name or op_type == "autodiff":
        return "backward", op_type
    return "forward", op_type


def module_of(op_name):
    """The model module of a device operation's ``op_name``: what the
    ``module.<name>`` scope that ``registry.lower_op`` opened round an op
    built inside ``fluid.name_scope(<name>)`` names (the innermost, where
    scopes nest), under any transform (``transpose(jvp(module.mtp))``) or
    rematerialisation, or ``""`` for an operation of no module or with no
    ``op_name``. ``region_of`` passes such a scope over: no op type is
    called so."""
    from .registry import MODULE_SCOPE

    module = ""
    for scope in (op_name or "").split("/")[:-1]:
        m = _SCOPE.match(scope)
        if m and m.group(1).startswith(MODULE_SCOPE):
            module = m.group(1)[len(MODULE_SCOPE):]
    return module


def device_time_by_region(profile, hlo_texts=None):
    """``{"phases": {phase: [calls, seconds]}, "ops": {(phase, op type):
    [calls, seconds]}, "instructions": {(instruction group, phase, op
    type): [calls, seconds]}, "busy_s": ...}`` over every device plane
    of a ``ProfileData``: each operation's duration under the region
    that its ``op_name`` names (a fusion's is its root's), and under
    its instruction's name without the number (``convert_reduce_fusion``).
    ``hlo_texts`` (``{module name: HLO text}``) is where the ``op_name``
    of an instruction is found; an operation is of the module whose run
    on the ``XLA Modules`` line contains it. ``busy_s`` is the union of
    the operations' intervals (summed over devices), to hold the rows'
    sum against."""
    import bisect

    op_names = {name: op_names_of(text)
                for name, text in (hlo_texts or {}).items()}
    runs = {}   # plane -> [(start, end, module)], sorted
    for plane, _, e in _device_events(profile, DEVICE_MODULE_LINE):
        runs.setdefault(plane, []).append(
            (e.start_ns, e.start_ns + e.duration_ns, _module_name(e.name)))
    for spans in runs.values():
        spans.sort()
    phases = {p: [0, 0.0] for p in PHASES}
    ops, instructions, regions, intervals = {}, {}, {}, {}
    for plane, _, event in _device_events(profile):
        if _is_container(event.name):
            continue    # its body's operations are events of their own
        module = ""
        spans = runs.get(plane, ())
        i = bisect.bisect_right(spans, (event.start_ns, float("inf"))) - 1
        if i >= 0 and event.start_ns < spans[i][1]:
            module = spans[i][2]
        key = (module, event.name)
        found = regions.get(key)
        if found is None:
            name = _instruction(event.name)
            region = region_of(op_names.get(module, {}).get(name, ""))
            group = re.sub(r"[.\d]+$", "", name) or name
            found = regions[key] = (region, (group,) + region)
        region, group = found
        seconds = event.duration_ns * 1e-9
        for row in (phases[region[0]], ops.setdefault(region, [0, 0.0]),
                    instructions.setdefault(group, [0, 0.0])):
            row[0] += 1
            row[1] += seconds
        intervals.setdefault(plane, []).append(
            (event.start_ns, event.start_ns + event.duration_ns))
    busy_ns = sum(union_seconds(spans) for spans in intervals.values())
    return {"phases": phases, "ops": ops, "instructions": instructions,
            "busy_s": busy_ns * 1e-9}


def region_report(regions):
    """The table ``stop_profiler`` appends: rows by phase, by program
    op type, and by the largest instruction groups with what each is
    made of; columns calls / total / share of the phases' sum."""
    total = sum(t for _, t in regions["phases"].values())
    lines = ["------------------------->  Device time by region  "
             "<-------------------------", ""]
    if not total:
        return "\n".join(lines + ["no device operation in the trace"])
    fmt = "%-40s %10d %12.4f %7.2f%%"
    lines.append("%-40s %10s %12s %8s" % ("Region", "Calls", "Total(ms)",
                                          "Share"))
    for phase in PHASES:
        calls, seconds = regions["phases"][phase]
        lines.append(fmt % (phase, calls, seconds * 1e3,
                            100.0 * seconds / total))
    lines.append("%-40s %10s %12.4f" % ("device busy (union)", "",
                                        regions["busy_s"] * 1e3))
    lines.append("")
    for (phase, op_type), (calls, seconds) in sorted(
            regions["ops"].items(), key=lambda kv: -kv[1][1]):
        if op_type:
            lines.append(fmt % ("%s %s" % (phase, op_type), calls,
                                seconds * 1e3, 100.0 * seconds / total))
    # what the trace's own names are made of: the largest instruction
    # groups, each with the regions that make up most of it
    groups = {}
    for (group, phase, op_type), row in regions["instructions"].items():
        groups.setdefault(group, []).append(
            (row[1], row[0], ("%s %s" % (phase, op_type)).strip()))
    lines.append("")
    for group, rows in sorted(groups.items(),
                              key=lambda kv: -sum(r[0] for r in kv[1]))[:8]:
        seconds = sum(r[0] for r in rows)
        lines.append(fmt % (group, sum(r[1] for r in rows), seconds * 1e3,
                            100.0 * seconds / total))
        for part, calls, region in sorted(rows, reverse=True)[:4]:
            lines.append(fmt % ("    " + region, calls, part * 1e3,
                                100.0 * part / total))
    return "\n".join(lines)


def export_chrome_tracing(path, trace_dir=None, profile=None):
    """Write a chrome://tracing JSON (reference ``tools/timeline.py``
    emits the same format from its profile protos). With a device trace
    (``profile``, or the newest under ``trace_dir``) the program's spans
    are read from its ``/host:CPU`` plane as pid 0 and the device's
    operations as pid 1, one clock; without one, pid 0 holds the ring's
    spans on ``perf_counter``."""
    import json

    if profile is None and trace_dir:
        profile = _load_trace(trace_dir)
    events = []
    if profile is None:
        for name, _, t_start, dur in recent_spans():
            events.append({"name": name, "ph": "X", "pid": 0, "tid": 0,
                           "ts": t_start * 1e6, "dur": dur * 1e6,
                           "cat": "host"})
    else:
        ours = PHASE_SPANS | set(_events)
        for plane in profile.planes:
            if plane.name != HOST_PLANE:
                continue
            for tid, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name in ours:
                        events.append({
                            "name": ev.name, "ph": "X", "pid": 0,
                            "tid": tid, "ts": ev.start_ns / 1e3,
                            "dur": ev.duration_ns / 1e3, "cat": "host"})
        for _, tid, ev in _device_events(profile):
            events.append({
                "name": _instruction(ev.name)[:120],
                "ph": "X", "pid": 1, "tid": tid, "ts": ev.start_ns / 1e3,
                "dur": ev.duration_ns / 1e3, "cat": "device"})
    meta = [{"name": "process_name", "ph": "M", "pid": 0,
             "args": {"name": "host"}},
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "device (XLA ops)"}},
            # how many host spans the buffer dropped — a trace that hit
            # _MAX_SPANS is TRUNCATED and must say so
            {"name": "dropped_spans", "ph": "M", "pid": 0,
             "args": {"count": _dropped[0]}}]
    with open(path, "w") as f:
        json.dump({"traceEvents": meta + events,
                   "displayTimeUnit": "ms"}, f)
    return path


def reset_profiler():
    global _spans
    _events.clear()
    if _spans.maxlen != _MAX_SPANS:
        # _MAX_SPANS was adjusted after import (tests shrink it); the
        # ring's maxlen is fixed at construction, so rebuild
        _spans = deque(maxlen=_MAX_SPANS)
    else:
        _spans.clear()
    _dropped[0] = 0


def summary(sorted_key=None):
    """Reference-shaped table: Event Calls Total Min Max Ave Ratio."""
    total_all = sum(e[1] for e in _events.values()) or 1e-12
    rows = []
    for name, (calls, total, mn, mx) in _events.items():
        rows.append((name, calls, total, mn, mx, total / calls,
                     total / total_all))
    if sorted_key in ("total", "calls", "max", "min", "ave"):
        idx = {"calls": 1, "total": 2, "min": 3, "max": 4, "ave": 5}[sorted_key]
        rows.sort(key=lambda r: r[idx], reverse=sorted_key != "min")
    lines = ["------------------------->  Profiling Report  "
             "<-------------------------", "",
             "%-40s %8s %12s %12s %12s %12s %8s" % (
                 "Event", "Calls", "Total(ms)", "Min(ms)", "Max(ms)",
                 "Ave(ms)", "Ratio")]
    for name, calls, total, mn, mx, ave, ratio in rows:
        lines.append("%-40s %8d %12.4f %12.4f %12.4f %12.4f %7.2f%%" % (
            name[:40], calls, total * 1e3, mn * 1e3, mx * 1e3, ave * 1e3,
            ratio * 100))
    return "\n".join(lines)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None,
             tracer_option="Default", trace_dir=None, timeline_path=None):
    """Reference ``fluid.profiler.profiler`` context manager."""
    reset_profiler()
    start_profiler(state, tracer_option, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path, timeline_path)


@contextlib.contextmanager
def cuda_profiler(*a, **kw):
    """Device traces come from jax.profiler; kept for API parity."""
    yield


npu_profiler = cuda_profiler
