"""Executor: runs Programs by lowering blocks to compiled XLA computations.

Capability parity: reference ``python/paddle/fluid/executor.py:418`` and C++
``framework/executor.cc`` — feed/fetch, scope-held persistable state, startup
program execution, compile caching.

TPU-first redesign: instead of an op-by-op interpreter hot loop
(``executor.cc:445``), the whole block is traced ONCE into a pure function

    step(state_dict, feed_dict, rng_key) -> (fetches, new_state, new_key)

jit-compiled with buffer donation on ``state`` (the XLA analogue of the
reference's in-place scope mutation + eager GC: donation lets XLA reuse
parameter buffers for their updated values, so an optimizer step is
allocation-free). Recompilation is avoided via a cache keyed on
(program identity, mutation counter, feed signature, fetch list).

Data-parallel / model-parallel execution reuses the same lowered function
under a ``jax.sharding.Mesh`` with GSPMD shardings supplied by
``CompiledProgram`` (see ``compiler.py``) — the reference's multi-device
SSA-graph executor (``details/fast_threaded_ssa_graph_executor.cc``) is
replaced by XLA partitioning + ICI collectives.
"""

import os
import time
import types
import weakref

import numpy as np

from . import compile_cache as _compile_cache
from . import framework
from . import monitor as _monitor
from . import profiler as _prof
from . import rng as _rng
from .framework import Program, Variable, convert_dtype
from .registry import LowerCtx, lower_block

__all__ = ["Executor", "Scope", "global_scope", "scope_guard",
           "register_run_hook", "unregister_run_hook"]

# -- monitor series (process-wide; see fluid/monitor.py) ----------------------
_M_RUN_SECONDS = _monitor.histogram(
    "executor_run_seconds",
    help="Executor.run wall time (feed normalization + compile-cache "
         "lookup + dispatch; includes device sync only while profiling)")
_M_RUNS = _monitor.counter(
    "executor_run_total", help="completed Executor.run calls")
_M_CACHE_HIT = _monitor.counter(
    "executor_compile_cache_hit_total",
    help="Executor.run served by an already-jitted step")
_M_CACHE_MISS = _monitor.counter(
    "executor_compile_cache_miss_total",
    help="Executor.run that traced+jitted a new step "
         "(program/feed-signature/fetch-list/sharding change)")
# tier-labeled views of the same series (the unlabeled legacy counters
# keep their exact semantics): tier=memory is this process's dict,
# tier=disk (owned by fluid/compile_cache.py) is the persistent tier a
# restart hits
_M_CACHE_HIT_MEM = _monitor.counter(
    "executor_compile_cache_hit_total",
    help="compile-cache hits by tier",
    labels={"tier": "memory"})
_M_CACHE_MISS_MEM = _monitor.counter(
    "executor_compile_cache_miss_total",
    help="compile-cache misses by tier",
    labels={"tier": "memory"})
_M_BATCHED_RUNS = _monitor.counter(
    "executor_batched_run_total",
    help="Executor.run calls that lowered iters>1 steps into one "
         "device-side loop (lax.scan) dispatch")
_M_BATCHED_ITERS = _monitor.counter(
    "executor_batched_iters_total",
    help="device-side training steps executed inside batched runs "
         "(sum of iters over executor_batched_run_total)")
_M_FETCH_SYNC = _monitor.histogram(
    "executor_fetch_sync_seconds",
    help="device->host fetch materialization (the blocking sync): "
         "return_numpy=True observes once per fetch at run time, "
         "fetch_mode='async' only when FetchHandle.numpy()/indexing "
         "forces the value — zero samples means no host sync happened")
_M_WINDOW_STALL = _monitor.histogram(
    "executor_window_stall_seconds",
    help="host wait for a prefetched iters=k window to finish its "
         "drain+stack+stage (0 when the window was already staged — "
         "the prefetch fully hid the host-side feed work)")
_M_OVERLAP_HIT = _monitor.counter(
    "executor_window_overlap_hit_total",
    help="batched runs served by an already-prefetched window "
         "(drain/stack/stage overlapped the previous window's compute)")
_M_OVERLAP_MISS = _monitor.counter(
    "executor_window_overlap_miss_total",
    help="prefetch-requested batched runs that drained inline "
         "(first window of a pass, or the pass just restarted after EOF)")
_M_PREFETCH_INFLIGHT = _monitor.gauge(
    "executor_window_prefetch_inflight",
    help="window prefetches currently draining/staging in the "
         "background (0 or 1 per Executor)")
_M_ANOMALY = _monitor.counter(
    "executor_anomaly_nonfinite_total",
    help="steps whose fetches/updated state contained non-finite values "
         "(or an injected step.nonfinite fault)")
_M_ANOMALY_SKIPPED = _monitor.counter(
    "executor_anomaly_skipped_steps_total",
    help="training steps discarded (state not committed) by the "
         "skip_step anomaly policy")
_M_ANOMALY_ROLLBACKS = _monitor.counter(
    "executor_anomaly_rollbacks_total",
    help="rollback-policy restores to the last intact checkpoint after "
         "a non-finite step")

# -- run hooks ----------------------------------------------------------------
_RUN_HOOKS = []


def register_run_hook(fn):
    """Register ``fn(record)`` to fire once after every completed
    ``Executor.run`` (the compiled-step path; server loops and EOF'd
    py_reader runs never complete a step). ``record`` keys:
    ``program_id`` (Program._uid), ``fetch_names``, ``wall_time``
    (seconds), ``cache_hit``, ``profiler_enabled``. A step-batched run
    (``Executor.run(..., iters=k)`` with k >= 2) still fires the hook
    ONCE for the whole device-side loop and adds an ``iters`` key
    (``record["iters"] == k``); single-step runs carry no ``iters`` key
    (read ``record.get("iters", 1)``). Hook exceptions are
    logged and swallowed — observability must not fail training.
    Returns ``fn`` so it composes as a decorator."""
    _RUN_HOOKS.append(fn)
    return fn


def unregister_run_hook(fn):
    """Remove a previously registered run hook (no-op if absent)."""
    try:
        _RUN_HOOKS.remove(fn)
    except ValueError:
        pass


def _fire_run_hooks(record):
    for fn in list(_RUN_HOOKS):
        try:
            fn(record)
        except Exception:
            import logging

            logging.getLogger(__name__).exception(
                "executor run hook %r failed", fn)


class Scope:
    """name -> device array store (reference ``framework/scope.h:46``)."""

    def __init__(self, parent=None):
        self.vars = {}
        self.parent = parent
        self.kids = []

    def new_scope(self):
        s = Scope(self)
        self.kids.append(s)
        return s

    def find_var(self, name):
        s = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def has_var(self, name):
        return self.find_var(name) is not None

    def set_var(self, name, value):
        self.vars[name] = value

    def erase(self, name):
        self.vars.pop(name, None)

    def drop_kids(self):
        self.kids = []

    def local_var_names(self):
        return list(self.vars)

    def var_names(self):
        """All visible names: this scope plus ancestors (find_var order;
        shadowed ancestor names appear once)."""
        seen, s = [], self
        while s is not None:
            for n in s.vars:
                if n not in seen:
                    seen.append(n)
            s = s.parent
        return seen


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope():
    return _scope_stack[-1]


import contextlib


@contextlib.contextmanager
def scope_guard(scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


RNG_STATE_VAR = "@rng_state@"


def _feed_signature(feed, block):
    sig = []
    for name in sorted(feed):
        arr = feed[name]
        dt = getattr(arr, "dtype", None)  # avoid np.asarray on device arrays
        if dt is None:
            dt = np.asarray(arr).dtype
        sig.append((name, tuple(np.shape(arr)), str(dt)))
    return tuple(sig)


def _split_batched_feed(feed, block, iters, batch_factor=1):
    """Classify each ``iters=k`` feed as per-iteration STACKED
    (``[k, ...]``, sliced by the device-side loop) or loop-INVARIANT
    (the per-step shape, reused every iteration).

    Vars with a fully static declared shape are validated exactly;
    when the declared shape has dynamic (-1) batch dims, the leading
    axis decides: ``shape[0] == k`` means one slice per iteration.
    Ambiguity (a per-step shape whose own leading dim equals k)
    resolves to the declared/per-step reading for static vars and the
    stacked reading for dynamic ones — stack explicitly to be safe.

    ``batch_factor > 1`` (manual pipeline mode): programs traced at the
    per-shard microbatch size take per-step feeds at the FULL batch —
    leading dim scaled by ``M * data * host`` — so that scaled shape is
    accepted alongside the declared one (batch-invariant feeds like an
    attention bias still arrive at their declared shape)."""
    stacked, invariant = {}, {}
    for name, arr in feed.items():
        shape = tuple(np.shape(arr))
        var = block._find_var_recursive(name)
        declared = tuple(int(d) for d in var.shape) \
            if var is not None and var.shape is not None else None
        static = declared is not None and all(d >= 0 for d in declared)
        if static:
            per_step = {declared}
            if batch_factor > 1 and declared and declared[0] > 0:
                per_step.add((declared[0] * batch_factor,) + declared[1:])
            if shape in per_step:
                invariant[name] = arr
            elif shape[:1] == (iters,) and shape[1:] in per_step:
                stacked[name] = arr
            elif shape[:1] == (iters,):
                raise ValueError(
                    "iters=%d: stacked feed %r has per-step shape %s "
                    "but var %r declares shape %s"
                    % (iters, name, list(shape[1:]), name,
                       list(declared)))
            else:
                raise ValueError(
                    "iters=%d: feed %r has shape %s — pass either the "
                    "per-step shape %s (reused every iteration) or a "
                    "leading-axis stack %s (one slice per iteration)"
                    % (iters, name, list(shape), list(declared),
                       [iters] + list(declared)))
        else:
            if shape[:1] == (iters,):
                stacked[name] = arr
            else:
                invariant[name] = arr
    return stacked, invariant


_HOST_OPS = frozenset(("listen_and_serv", "fl_listen_and_serv",
                       "host_embedding_init", "py_reader_dequeue", "save"))


def _host_plan(program):
    """What a Program asks of the host around its compiled step, from ONE
    walk of its ops - the one place the executor knows the op types of
    the layers above it: ``server`` (a pserver program does not compile:
    its op is a host serving loop, like the reference's
    listen_and_serv_op.cc RunSyncLoop), ``tables`` (host embedding
    tables whose residency resets with this run), ``reader_ids`` (the
    py_reader queues that feed it) and ``saves`` (name, path)."""
    block = program.global_block()
    plan = types.SimpleNamespace(server=None, tables=[], reader_ids=[],
                                 saves=[])
    for blk in program.blocks:
        for op in blk.ops:
            if op.type not in _HOST_OPS:
                continue
            if op.type == "save":
                # save ops write once per run, after commit - which is
                # only truthful at the top level. Inside control flow (a
                # cond branch that may not run, a While body that may run
                # 0 or N times) a host file write cannot follow the
                # predicate from within one compiled step, so refuse
                # rather than silently firing.
                if blk is not block:
                    raise RuntimeError(
                        "a save op inside a control-flow sub-block is not "
                        "supported: the compiled step cannot conditionally "
                        "write host files — move the save op to the global "
                        "block or checkpoint from the host loop "
                        "(fluid.io.save)")
                plan.saves.append((op.input("X")[0], op.attr("file_path")))
            elif blk is not block:
                continue
            elif op.type == "host_embedding_init":
                # host-side residency reset, synchronous with the run -
                # the in-program op is a no-op (an io_callback there fires
                # on a runtime thread after the async dispatch returns,
                # racing the next step's residency prepare and wiping the
                # LUT it just admitted)
                plan.tables.append(op.attr("table_name"))
            elif op.type == "py_reader_dequeue":
                plan.reader_ids.append(int(op.attr("reader_id")))
            elif plan.server is None:
                plan.server = op
    return plan


def _serve(op, scope):
    """Run a server program's host loop until it ends."""
    if op.type == "listen_and_serv":
        from .transpiler.distribute_transpiler import build_server_from_attrs

        build_server_from_attrs(op.attrs).serve_forever()
        return
    # federated variant (reference fl_listen_and_serv_op): initial params
    # come from this scope's vars by name
    from ..distributed import fl_server as _fl

    params = {}
    for name in op.attr("param_names"):
        val = scope.find_var(name)
        if val is None:
            raise RuntimeError(
                "fl_listen_and_serv param %r not in scope — "
                "run the startup program first" % name)
        params[name] = np.asarray(val)
    configured = op.attr("endpoint")
    host, port = configured.rsplit(":", 1)
    srv = _fl.FLServer(params, op.attr("n_trainers"),
                       host=host, port=int(port))
    # register under BOTH the endpoint the program named and the socket's
    # resolved one (getsockname may differ, e.g. localhost vs 127.0.0.1)
    for key in {configured, srv.endpoint}:
        _fl.SERVING[key] = srv
    try:
        srv.serve_forever()
    finally:
        srv.stop()
        for key in {configured, srv.endpoint}:
            _fl.SERVING.pop(key, None)


def _drain_window(readers, iters):
    """Pull exactly ``iters`` batches from every reader and stack them
    ``[k, ...]``: ``("ok", feed)``, or ``("eof", n_pulled, partial)``
    when a queue ran out first (``partial``: batches were pulled and are
    lost, so size the pass to a multiple of k to lose nothing)."""
    pulled = {r: [] for r in readers}
    for i in range(iters):
        step_vals = [(r, r._next()) for r in readers]
        if any(v is None for _, v in step_vals):
            return ("eof", i,
                    bool(i) or any(v is not None for _, v in step_vals))
        for r, vals in step_vals:
            pulled[r].append(vals)
    return ("ok", {name: np.stack([vals[j] for vals in items])
                   for r, items in pulled.items()
                   for j, name in enumerate(r.names)})


def _trace_step(block, fetch_names, strategy=None, fixed_state=False):
    """The block as the pure function ``step(state, feed_vals, rng_key) ->
    (fetches, new_state, new_key)`` every compiled program is made of."""
    mesh = strategy.mesh if strategy is not None else None

    def step(state, feed_vals, rng_key):
        env = {}
        env.update(state)
        env.update(feed_vals)
        ctx = LowerCtx(block, env, _rng.wrap_key_data(rng_key), mesh=mesh)
        if strategy is not None:
            strategy._on_trace_begin(ctx)
        lower_block(ctx, block)
        fetches = [ctx.get(n) for n in fetch_names]
        # Return ALL state (unchanged entries pass through as aliased
        # buffers under donation — returning them keeps the donated
        # buffers alive for the scope), plus vars that became
        # persistable during this program (startup init).
        new_state = {n: env[n] for n in state if n in env}
        grown = {n: env[n] for n in ctx.written
                 if n in env and n not in new_state}
        grown.update((name, env[name]) for name, var in block.vars.items()
                     if var.persistable and name in env
                     and name not in state)
        if grown and fixed_state:
            # a scan carry has a FIXED structure: a program that creates
            # new persistables mid-step (startup-style init) cannot be
            # step-batched — fail with the remedy, not a tracer error
            raise RuntimeError(
                "iters>1 needs loop-invariant state, but this "
                "program creates new persistable vars %s during "
                "the step — run the startup program (iters=1) "
                "first so they exist in the scope" % (sorted(grown),))
        new_state.update(grown)
        return fetches, new_state, _rng.key_data(ctx.rng_key)

    # what a trace calls the program: XLA's module (``jit_train_step``)
    # and the ``jit(train_step)`` that leads every operation's op_name
    if any(op.type == "autodiff" for op in block.ops):
        step.__name__ = "train_step"
    return step


def _scan_steps(step, iters):
    """``step`` wrapped in a ``lax.scan`` over the iteration axis: stacked
    feeds are sliced per step, invariant feeds close over the loop and
    ``(state, rng)`` is the carry."""
    import jax

    def batched_step(state, stacked_feeds, invariant_feeds, rng_key):
        def body(carry, feed_i):
            st, rk = carry
            fv = dict(invariant_feeds)
            fv.update(feed_i)
            fetches, new_st, new_rk = step(st, fv, rk)
            return (new_st, new_rk), fetches

        (final_state, final_rng), traj = jax.lax.scan(
            body, (state, rng_key), stacked_feeds, length=iters)
        return traj, final_state, final_rng

    return batched_step


def _state_names(program, scope):
    """The persistable state visible to ``program`` in ``scope``."""
    return sorted(v.name for v in program.list_vars()
                  if v.persistable and scope.has_var(v.name))


def _local_view(x):
    """Host-readable numpy view of a possibly multi-process array: a
    non-fully-addressable array (replicated or sharded across processes)
    is read through its first LOCAL shard — the shard-local view every
    SPMD process can materialize without a cross-host gather. The one
    conversion helper shared by the sync fetch path, the async
    ``FetchHandle``, save ops, and the nan/inf debug checks."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        return np.asarray(x.addressable_shards[0].data)
    return np.asarray(x)


def _fetch_numpy(x):
    """Materialize one fetch on the host (THE blocking device sync —
    observed by ``executor_fetch_sync_seconds``), multiprocess-safe: a
    replicated global array reads its local replica; a SHARDED global
    fetch has no complete local value, so fail loudly rather than
    return a slice."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable \
            and not getattr(x.sharding, "is_fully_replicated", False):
        raise ValueError(
            "fetch is sharded across processes (%s); fetch with "
            "return_numpy=False and gather explicitly (e.g. "
            "multihost_utils.process_allgather)" % (x.sharding,))
    with _M_FETCH_SYNC.time():
        return _local_view(x)


class FetchHandle:
    """A fetch result still in flight on the device
    (``Executor.run(..., fetch_mode="async")``).

    JAX dispatch is asynchronous: ``run`` returns as soon as the step is
    enqueued, and the handle wraps the resulting ``jax.Array`` WITHOUT
    forcing a device->host sync — back-to-back windows keep the device
    busy. The sync happens exactly when you ask for host data:
    ``.numpy()``, indexing, ``np.asarray(handle)``, or ``float(handle)``
    (each observes ``executor_fetch_sync_seconds``). ``.value`` exposes
    the raw in-flight array and ``shape``/``dtype``/``repr`` never
    sync."""

    __slots__ = ("_value", "name")

    def __init__(self, value, name=None):
        self._value = value
        self.name = name

    @property
    def value(self):
        """The underlying (possibly in-flight) array — no sync."""
        return self._value

    @property
    def shape(self):
        return tuple(np.shape(self._value))

    @property
    def dtype(self):
        return getattr(self._value, "dtype", None)

    def block_until_ready(self):
        """Wait for the device computation, keep data on device (no
        transfer). Returns self for chaining."""
        import jax

        jax.block_until_ready(self._value)
        return self

    def numpy(self):
        """Materialize on the host (blocking sync)."""
        with _prof.RecordEvent(_prof.SPAN_FETCH):
            return _fetch_numpy(self._value)

    def __getitem__(self, idx):
        return self.numpy()[idx]

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(self.numpy())

    def __repr__(self):
        return "FetchHandle(name=%r, shape=%s, dtype=%s)" % (
            self.name, self.shape, self.dtype)


class _CompiledStep:
    """One jit-compiled (program block, feed-sig, fetch-list) entry."""

    def __init__(self, fn, state_names, fetch_names):
        self.fn = fn
        self.state_names = state_names
        self.fetch_names = fetch_names
        self.arg_specs = None   # the first call's arguments, as shapes
        _LIVE_STEPS.add(self)

    def note_args(self, args):
        """Keep the shapes of a call's arguments (taken before the call:
        it donates the state), for ``hlo_text``."""
        import jax

        def spec(x):
            # an uncommitted array's placement is jit's to choose, as
            # the call's own lowering had it: naming it would make
            # another program of the same step, compiled anew
            committed = isinstance(x, jax.Array) and x.committed
            return jax.ShapeDtypeStruct(
                np.shape(x), x.dtype,
                sharding=x.sharding if committed else None)

        self.arg_specs = jax.tree_util.tree_map(spec, args)

    def hlo_text(self):
        """The compiled module as HLO text, each instruction with the
        ``op_name`` it was traced under: what the profiler's region
        table reads, since the device trace names an operation but does
        not carry its metadata. From ``profiler.step_lowering`` (the
        newest step shares its one lowering with the region table and
        ``newest_step_memory``); None where the step cannot be lowered
        (never called, or a disk-tier or sharded wrapper)."""
        lowering = _prof.step_lowering(self.fn, self.arg_specs)
        return lowering[0] if lowering is not None else None


_LIVE_STEPS = weakref.WeakSet()


def compiled_steps():
    """Every compiled step some Executor still holds
    (``profiler.stop_profiler`` asks them for their HLO text)."""
    return list(_LIVE_STEPS)


class _WindowPrefetch:
    """One in-flight background drain+stack+stage of the NEXT ``iters=k``
    py_reader window (``Executor.run(..., iters=k, prefetch=True)``).

    While the device executes window i, this thread pulls the k batches
    of window i+1 from the py_reader queues, stacks them ``[k, ...]``
    (``_to_arrays`` already normalized dtype/shape to the declared
    slots), and ``jax.device_put``s the stacks with the program's GSPMD
    feed sharding (``CompiledProgram.feed_sharding`` at ``batch_dim=1``
    — axis 0 is the iteration axis) — so when window i's dispatch
    returns, window i+1's feeds are already device-resident and
    pre-sharded. EOF is detected here but ACTED ON at consume time: the
    consuming run resets the readers and raises ``EOFException`` before
    any step executes, preserving the inline path's
    EOF-before-step contract.

    The thread is NON-daemon (tests/conftest.py fails tests that leak
    one); ``consume()``/``discard()`` join it. ``_next()`` only blocks
    as long as the user's generator takes to yield, so the join is
    bounded by one window of host feed work."""

    def __init__(self, py_readers, iters, sharding_fn=None):
        import threading

        self.key = (tuple(id(r) for r in py_readers), iters)
        self.readers = list(py_readers)
        self.iters = iters
        self._sharding_fn = sharding_fn
        self._result = ("error", RuntimeError("prefetch never ran"))
        self._thread = threading.Thread(
            target=self._drain, name="paddle-window-prefetch",
            daemon=False)
        self._thread.start()

    def _drain(self):
        import jax

        try:
            with _M_PREFETCH_INFLIGHT.track():
                result = _drain_window(self.readers, self.iters)
                if result[0] == "ok":
                    feed = {}
                    for name, arr in result[1].items():
                        s = self._sharding_fn(name, arr) \
                            if self._sharding_fn is not None else None
                        feed[name] = jax.device_put(arr, s) \
                            if s is not None else jax.device_put(arr)
                    result = ("ok", feed)
                self._result = result
        except BaseException as e:  # background thread: stored and re-raised on the consuming run
            self._result = ("error", e)

    def consume(self):
        """Join the drain and return ``("ok", feed)``, ``("eof",
        n_pulled, partial)`` or ``("error", exc)``. The join time IS
        the window stall — 0 when the prefetch finished during the
        previous window's compute."""
        import time as _time

        t0 = _time.perf_counter()
        self._thread.join()
        _M_WINDOW_STALL.observe(_time.perf_counter() - t0)
        return self._result

    def discard(self):
        """Join and drop the result (Executor.close / abandoned loop).
        Already-pulled batches are lost, like any abandoned pass."""
        self._thread.join()
        self._result = ("error", RuntimeError("prefetch discarded"))


class Executor:
    """Reference ``executor.py:418``. JAX device placement is controlled
    by the default backend / shardings, so ``place`` selects nothing —
    but a ``TPUPlace`` on a host whose default backend is not a TPU
    raises instead of training on the CPU without a word. ``None`` and
    ``CPUPlace`` run wherever JAX runs."""

    def __init__(self, place=None):
        from . import TPUPlace

        if isinstance(place, TPUPlace):
            import jax

            platform = jax.devices()[0].platform
            if platform != "tpu":
                raise RuntimeError(
                    "Executor(%r): JAX's default backend is %r, not a "
                    "tpu; pass no place to run there" % (place, platform))
        self.place = place
        self._cache = {}
        # extra read-only disk-cache tiers consulted on a memory miss
        # (e.g. a Predictor's model-adjacent __prelowered__ directory);
        # the env-configured PADDLE_COMPILE_CACHE_DIR joins implicitly
        self._cache_read_dirs = []
        # (reader ids, iters) -> in-flight _WindowPrefetch; one entry
        # per distinct prefetching batched loop (close() reaps them all)
        self._window_prefetch = {}
        # consecutive steps discarded by the skip_step/rollback anomaly
        # policy; a clean step resets it, exceeding the budget raises
        self._anomaly_skips = 0
        # donate the state dict to the step executable (training wants
        # the buffer reuse). Inference-path executors (Predictor,
        # prelower export) set this False: donation bakes input->output
        # aliasing into AOT-compiled executables — the ones the
        # persistent cache serializes — and on CPU those run IN-PLACE
        # over buffers that serving still exposes through zero-copy
        # numpy views, corrupting served results after a cache restore.
        # (A plain jit dispatch drops donation on CPU, which is why
        # only the deserialized/AOT path was exposed.) The bit joins
        # the disk cache key, so writer and reader must agree.
        self._donate_state = True

    # -- anomaly policy (nan/inf) --------------------------------------
    def _scan_anomaly(self, fetch_names, fetches, new_state):
        """First non-finite (kind, var name) among fetches and updated
        state, or None. Runs when FLAGS_check_nan_inf is on, when the
        anomaly policy is not 'raise', or when a step.nonfinite fault is
        armed; costs one host sync by design. Shard-local on
        multi-process arrays (every SPMD process scans its shard)."""
        from . import faults as _faults
        from . import flags as _flags

        enabled = (_flags.check_nan_inf_enabled()
                   or _flags.anomaly_policy() != "raise"
                   or _faults.is_armed("step.nonfinite"))
        if not enabled:
            return None
        if _faults.take("step.nonfinite"):
            return ("injected", "step.nonfinite")
        for label, vals in (("fetch", zip(fetch_names, fetches)),
                            ("state", new_state.items())):
            for n, v in vals:
                arr = _local_view(v)
                if np.issubdtype(arr.dtype, np.floating) and \
                        not np.isfinite(arr).all():
                    return (label, n)
        return None

    def _handle_anomaly(self, where, program, scope, checkpoint, iters):
        """Apply the configured anomaly policy to a non-finite step.
        Returns True when the step (or whole ``iters=k`` window) must be
        DISCARDED — the caller then commits neither state nor rng.

        ``raise``: legacy behavior, FloatingPointError names the var.
        ``skip_step``: drop this step's updates, keep training on the
        previous weights; after ``FLAGS_anomaly_skip_budget`` CONSECUTIVE
        anomalous steps it raises anyway (a persistently diverged run
        must not spin forever). ``rollback``: additionally restore the
        last intact checkpoint (requires ``checkpoint=(manager, n)`` on
        this run call), rewinding optimizer state and rng with the
        params. Skip/rollback keep the PRE-step scope arrays live, so
        they need XLA buffer donation off (the executor builds its plain
        jit undonated for these policies automatically; sharded runs set
        ``build_strategy.enable_inplace = False``)."""
        from . import flags as _flags

        _M_ANOMALY.inc()
        policy = _flags.anomaly_policy()
        msg = ("non-finite values in %s var %r after running program"
               % where)
        if policy == "raise":
            raise FloatingPointError("FLAGS_check_nan_inf: " + msg)
        self._anomaly_skips += 1
        budget = _flags.anomaly_skip_budget()
        if self._anomaly_skips > budget:
            raise FloatingPointError(
                "anomaly policy %r: %s — %d consecutive anomalous steps "
                "exceeded FLAGS_anomaly_skip_budget=%d"
                % (policy, msg, self._anomaly_skips, budget))
        import logging

        log = logging.getLogger(__name__)
        if policy == "rollback":
            if checkpoint is None:
                raise RuntimeError(
                    "anomaly policy 'rollback' needs a checkpoint to "
                    "roll back to — call Executor.run(..., "
                    "checkpoint=(CheckpointManager, every_n_steps))")
            step = checkpoint[0].restore(self, program, scope=scope)
            _M_ANOMALY_ROLLBACKS.inc()
            log.warning("anomaly policy rollback: %s; restored "
                        "checkpoint step %d (%d/%d consecutive)",
                        msg, step, self._anomaly_skips, budget)
        else:
            _M_ANOMALY_SKIPPED.inc(iters)
            log.warning("anomaly policy skip_step: %s; discarding the "
                        "step's updates (%d/%d consecutive)",
                        msg, self._anomaly_skips, budget)
        return True

    @staticmethod
    def _check_checkpoint_arg(checkpoint):
        if checkpoint is None:
            return None
        try:
            mgr, every = checkpoint
        except (TypeError, ValueError):
            raise ValueError(
                "checkpoint must be a (CheckpointManager, every_n_steps) "
                "pair, got %r" % (checkpoint,))
        if not hasattr(mgr, "step_completed") or int(every) < 1:
            raise ValueError(
                "checkpoint must be a (CheckpointManager, every_n_steps "
                ">= 1) pair, got %r" % (checkpoint,))
        return mgr, int(every)

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
        iters=1,
        fetch_mode=None,
        prefetch=False,
        checkpoint=None,
    ):
        """``iters=1`` (default): one feed/fetch step.

        ``iters=k`` (k >= 2): step-batched execution — the program's step
        function is compiled ONCE and ``k`` steps run inside a single
        jitted dispatch (``jax.lax.scan`` carrying ``(state, rng)`` with
        buffer donation), amortizing the per-step Python + PJRT round
        trip the way the reference's C++ hot loop (``executor.cc:445``)
        amortizes op dispatch. Feed contract: each feed is either a
        leading-axis stack ``[k, ...]`` (one slice per iteration) or the
        plain per-step shape (loop-invariant, reused every iteration);
        py_reader-fed programs instead drain exactly ``k`` batches up
        front. Each fetch returns the per-iteration trajectory, stacked
        ``[k, ...]``.

        ``fetch_mode="async"``: return ``FetchHandle`` objects instead
        of numpy — the step is dispatched but run() never blocks on a
        device->host sync; each handle syncs only when ``.numpy()`` /
        indexing forces it. ``fetch_mode="sync"`` (or None) is the
        legacy behavior, where ``return_numpy`` decides between numpy
        (blocking per fetch) and raw in-flight ``jax.Array``s.

        ``prefetch=True`` (needs ``iters=k`` and a py_reader-fed
        program): after dispatching this window, a background thread
        drains + stacks + device-stages window i+1's batches while the
        device executes window i, so the next ``run`` finds its feeds
        already staged (``executor_window_overlap_hit_total``).
        EOF-before-step semantics are preserved. See README "Async
        execution".

        ``checkpoint=(manager, every_n_steps)``: after every committed
        step (``iters=k`` counts k), the ``fluid.io.CheckpointManager``
        advances its step counter and writes a crash-consistent
        checkpoint each time it crosses a multiple of ``every_n_steps``
        — pair with ``manager.restore_on_restart`` for auto-resume under
        ``distributed.launch(max_restarts=...)``. Also the rollback
        target for the ``rollback`` anomaly policy (README "Fault
        tolerance")."""
        checkpoint = self._check_checkpoint_arg(checkpoint)
        if fetch_mode not in (None, "sync", "async"):
            raise ValueError(
                "fetch_mode must be None, 'sync' or 'async', got %r"
                % (fetch_mode,))
        iters = int(iters)
        if iters < 1:
            raise ValueError("iters must be >= 1, got %d" % iters)
        if prefetch and iters == 1:
            raise ValueError(
                "prefetch=True needs iters>=2: window prefetch overlaps "
                "the NEXT step-batched window with this one's compute — "
                "single steps already overlap via async dispatch "
                "(fetch_mode='async')")
        _prof.begin_run()
        try:
            t_run0 = time.perf_counter()
            with _prof.RecordEvent(_prof.SPAN_PREPARE):
                prep = self._prepare(program, feed, fetch_list, scope,
                                     iters, prefetch, checkpoint)
            if prep is None:    # a server program: its loop has ended
                return []
            return self._run_prepared(prep, t_run0, return_numpy, iters,
                                      fetch_mode, prefetch, checkpoint)
        finally:
            _prof.end_run()

    def _prepare(self, program, feed, fetch_list, scope, iters, prefetch,
                 checkpoint):
        """``executor.prepare``: everything from entry to the
        compile-cache lookup, for one step (``iters == 1``) or a window of
        k steps that one compiled executable drives device-side, its
        feeds drained or stacked ``[k, ...]`` here. Returns what
        ``_run_prepared`` takes, or None after a server program's loop."""
        import jax

        scope = scope or global_scope()
        feed = dict(feed or {})
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in fetch_list or []]

        # CompiledProgram carries sharding strategy; plain Program runs single-device.
        from . import compiler

        strategy = None
        if isinstance(program, compiler.CompiledProgram):
            strategy = program
            program = strategy._program
        if program is None:
            program = framework.default_main_program()
        block = program.global_block()

        # graceful preemption (distributed.preemption): launched workers
        # have PADDLE_PREEMPT_DRAIN=1, so the first run() installs the
        # SIGTERM drain handlers; a signal that already arrived drains
        # HERE — before the step or window, never inside one (the k-step
        # device loop is the commit unit) — through the active
        # CheckpointManager and exits 0 (drain_exit does not return).
        from ..distributed import preemption as _preemption

        _preemption.maybe_install_from_env()
        _preemption.check_drain(checkpoint[0] if checkpoint else None,
                                program, scope)

        plan = _host_plan(program)
        if plan.server is not None:
            if iters > 1:
                raise RuntimeError(
                    "iters>1 cannot drive a server program (%s op): the "
                    "serving loop runs on the host — call exe.run "
                    "without iters" % plan.server.type)
            _serve(plan.server, scope)
            return None
        if plan.tables:
            from .. import embedding as _embedding

            for name in plan.tables:
                _embedding.get_host_table(name).reset_residency()
        py_readers = []
        if plan.reader_ids:
            from .layers.py_reader import _READERS

            py_readers = [_READERS.get(rid) for rid in plan.reader_ids]
            if None in py_readers:
                raise RuntimeError(
                    "the py_reader feeding this program was "
                    "garbage-collected — keep the object returned "
                    "by layers.py_reader() alive and start() it")
        if prefetch and not py_readers:
            raise ValueError(
                "prefetch=True needs a py_reader-fed program — explicit "
                "feeds are the caller's to stage ahead of time "
                "(DataLoader use_double_buffer / fluid.reader.stage_feed)")

        # a window prefetched on these readers is this run's to consume
        # only if it was drained for the same readers at the same iters
        rkey = (tuple(id(r) for r in py_readers), iters)
        for k, pf in self._window_prefetch.items():
            if k == rkey or not set(k[0]) & set(rkey[0]):
                continue
            if iters == 1:
                raise RuntimeError(
                    "a prefetched iters=%d window is pending on "
                    "this program's py_reader(s) — a single-step "
                    "run would race it for batches. Finish the "
                    "batched loop (run with iters=%d until EOF) or "
                    "exe.close() first." % (pf.iters, pf.iters))
            raise RuntimeError(
                "a prefetched window (iters=%d) is pending on "
                "py_reader(s) this run (iters=%d) also reads — the "
                "prefetched batches would be mis-windowed. Keep a "
                "prefetching batched loop's iters uniform, or "
                "exe.close() between loops." % (pf.iters, iters))
        pending = self._window_prefetch.pop(rkey, None)

        # pull every reader's batches on the host BEFORE dispatch and ride
        # the normal feed path (works under any sharding strategy); any
        # empty queue raises EOF with no step run — nothing to discard,
        # donation stays on. All batches of a step are pulled before
        # deciding, so uneven readers lose at most the final ragged step
        # (logged), exactly one epoch ends.
        eof = None
        if py_readers and iters == 1:
            pulled = [(r, r._next()) for r in py_readers]
            if any(v is None for _, v in pulled):
                eof = ("py_reader queue exhausted — reader.reset() and "
                       "re-start() for the next pass")
                dropped = [r.names[0] for r, v in pulled if v is not None]
                if dropped:
                    import logging

                    logging.getLogger(__name__).warning(
                        "py_reader EOF: discarding the already-pulled "
                        "batch of %s (readers have unequal lengths)",
                        dropped)
            else:
                for r, vals in pulled:
                    feed.update(zip(r.names, vals))
        elif py_readers:
            if pending is not None:
                # overlap hit: the window was drained+stacked+staged in
                # the background while the previous window computed
                status = pending.consume()
                if status[0] == "error":
                    raise status[1]
            else:
                if prefetch:
                    # first window of a pass (or the pass just restarted
                    # after EOF): nothing staged yet, drain inline
                    _M_OVERLAP_MISS.inc()
                status = _drain_window(py_readers, iters)
            if status[0] == "eof":
                eof = ("py_reader queue exhausted before %d batches — "
                       "reader.reset() and re-start() for the next pass"
                       % iters)
                if status[2]:
                    import logging

                    logging.getLogger(__name__).warning(
                        "py_reader EOF during a %sbatched run: "
                        "discarding %d already-pulled batch(es) of a "
                        "requested window of %d",
                        "prefetched " if pending is not None else "",
                        status[1], iters)
            else:
                if pending is not None:
                    _M_OVERLAP_HIT.inc()
                feed.update(status[1])
        if eof is not None:
            from . import core as _core

            for r in py_readers:
                r.reset()
            raise _core.EOFException(eof)

        # host-tier embedding tables: translate the raw ids of this batch
        # (or of the whole [k, ...] window, in one residency transaction,
        # so the scanned body only ever gathers resident slots) into
        # resident-cache slots, admitting missing rows, and inject the
        # <table>@SLOTS feed — BEFORE normalization so the slots array is
        # part of the feed signature like any other input
        if getattr(program, "_embedding_bindings", None):
            from .. import embedding as _embedding

            _embedding.prepare_feed(program, feed, scope, iters=iters)

        # normalize feeds to declared dtype; device-resident jax Arrays pass
        # through untouched (the DataLoader/buffered-reader path pre-stages
        # H2D transfers — critical when the chip sits behind a slow link)
        from .lod import LoDTensor, lod_name

        for name in list(feed):
            if isinstance(feed[name], LoDTensor):
                if iters > 1:
                    raise ValueError(
                        "iters>1 does not take LoDTensor feeds — feed "
                        "dense arrays (plus explicit length arrays) "
                        "stacked [k, ...], or loop exe.run from the host")
                # decompose: data under the name, int32 lengths under @LOD
                # (the bounded-LoD device encoding, see fluid/lod.py)
                feed[lod_name(name)] = feed[name].lengths()
                feed[name] = feed[name].data()
            if isinstance(feed[name], jax.Array):
                continue
            var = block._find_var_recursive(name)
            arr = np.asarray(feed[name])
            if var is not None and arr.dtype != var.dtype:
                arr = arr.astype(var.dtype)
            feed[name] = arr

        feeds = (feed,)
        if iters > 1:
            batch_factor = 1
            if strategy is not None and \
                    getattr(strategy, "_mode", "") == "pipeline":
                batch_factor = int(strategy._num_microbatches)
                mesh = strategy.mesh
                for ax in ("host", "data"):
                    if mesh is not None and ax in mesh.shape:
                        batch_factor *= int(mesh.shape[ax])
            feeds = _split_batched_feed(feed, block, iters, batch_factor)

        state_names = _state_names(program, scope)
        feed_sig = _feed_signature(feed, block)

        from . import flags as _flags

        # program._uid (a monotonic token) rather than id(program): a GC'd
        # Program's id can be reused, which would serve a stale compiled
        # step. A k-step executable is another program than a single
        # step, so iters is a member. The anomaly-policy bit joins the key
        # because it flips buffer donation (skip_step/rollback must keep
        # pre-step buffers alive).
        key = (
            program._uid,
            program._mutation,
            feed_sig,
            tuple(fetch_names),
            tuple(state_names),
            strategy._uid if strategy is not None else 0,
            iters,
            _flags.anomaly_policy() != "raise",
        )

        state, rng = self._state_and_rng(program, scope, state_names)
        return types.SimpleNamespace(
            program=program, strategy=strategy, scope=scope,
            fetch_names=fetch_names, save_ops=plan.saves, key=key,
            feed_names=list(feed), feeds=feeds, state=state, rng=rng,
            py_readers=py_readers,
            build=lambda: self._build(program, block, feeds, feed_sig,
                                      fetch_names, state_names, strategy,
                                      iters))

    @staticmethod
    def _state_and_rng(program, scope, state_names):
        """The step's state arguments, out of the scope. The rng state
        persists across runs there."""
        rng = scope.find_var(RNG_STATE_VAR)
        if rng is None:
            seed = program.random_seed or 0
            rng = _rng.key_data(_rng.root_key(seed))
            scope.set_var(RNG_STATE_VAR, rng)
        return {n: scope.find_var(n) for n in state_names}, rng

    def _run_prepared(self, p, t_run0, return_numpy, iters, fetch_mode,
                      prefetch, checkpoint):
        """The rest of a run, single step or ``iters=k`` window alike:
        the compile-cache lookup, ``executor.compile`` or
        ``executor.call``, ``executor.commit``, ``executor.fetch``. ``p``
        is what ``_prepare`` returned."""
        import jax

        from . import flags as _flags
        from .. import telemetry as _telemetry
        from ..distributed import preemption as _preemption

        program, scope, fetch_names = p.program, p.scope, p.fetch_names
        batched = iters > 1
        step = self._cache.get(p.key)
        cache_hit = step is not None
        (_M_CACHE_HIT if cache_hit else _M_CACHE_MISS).inc()
        (_M_CACHE_HIT_MEM if cache_hit else _M_CACHE_MISS_MEM).inc()
        profiling = _prof.is_profiler_enabled()
        with _prof.RecordEvent(_prof.SPAN_CALL if cache_hit
                               else _prof.SPAN_COMPILE):
            if step is None:
                if _flags.check_program_enabled():
                    # debug mode (reference multi_devices_check_pass):
                    # validate well-formedness once per compiled signature
                    from .passes import apply_pass

                    apply_pass(program, "program_check",
                               feed_names=p.feed_names)
                step = self._cache[p.key] = p.build()
                step.note_args((p.state, *p.feeds, p.rng))
                _prof.note_compiled_step(step.fn, step.arg_specs)
            t0 = _prof.now()
            try:
                if _telemetry.enabled() and \
                        _telemetry.current() is not None:
                    # traced request (serving batch ctx is ambient): the
                    # device-dispatch interval joins the request's trace
                    attrs = {"program": program._uid}
                    if batched:
                        attrs["iters"] = iters
                    else:
                        attrs["cache_hit"] = cache_hit
                    with _telemetry.span(
                            "executor.run_batched" if batched
                            else "executor.run", attrs=attrs):
                        fetches, new_state, new_rng = step.fn(
                            p.state, *p.feeds, p.rng)
                else:
                    fetches, new_state, new_rng = step.fn(
                        p.state, *p.feeds, p.rng)
            except Exception:
                # flight-recorder trigger: capture the ring (open spans
                # show the in-flight request) before the failure unwinds
                _telemetry.flight.dump(reason="executor_exception")
                raise
        if profiling:
            # the table's event is the step's time, so it waits for the
            # device - unless the profiler is taking a device trace, which
            # the wait would distort. The #p<uid> suffix keeps distinct
            # programs with the same leading fetches apart in the table,
            # and out of the monitor's label space.
            if _prof.times_runs():
                jax.block_until_ready(fetches)
            event = "%s[%s#p%d%s]" % (
                "executor_batched_run" if batched else "executor_run",
                ",".join(fetch_names[:3]), program._uid,
                ";k=%d" % iters if batched else "")
            _prof._record(event, _prof.now() - t0, series=False)
        if prefetch:
            # dispatch is asynchronous — window i is still executing on
            # device; start draining + staging window i+1 right now so
            # the next run finds it ready (overlap hit). Pre-shard with
            # the program's GSPMD feed sharding (iteration axis is 0,
            # so the dp'd batch axis sits at 1).
            sharding_fn = None
            if p.strategy is not None and p.strategy.mesh is not None:
                sharding_fn = (lambda name, v:
                               p.strategy.feed_sharding(v, batch_dim=1))
            pf = _WindowPrefetch(p.py_readers, iters, sharding_fn)
            self._window_prefetch[pf.key] = pf
        with _prof.RecordEvent(_prof.SPAN_COMMIT):
            # nan/inf anomaly scan BEFORE commit (reference
            # FLAGS_check_nan_inf / nan_inf_utils, grown into a policy): a
            # non-finite step is handled per FLAGS_anomaly_policy — raise
            # (legacy, default), skip_step (discard the update), or
            # rollback (restore the last checkpoint). Discarded steps
            # commit nothing. Under iters=k the granularity is the
            # WINDOW: a non-finite value anywhere in the k-step
            # trajectory (fetches are stacked [k, ...]) or the final
            # state discards all k steps — the device-side loop cannot
            # partially commit.
            anomaly = self._scan_anomaly(fetch_names, fetches, new_state)
            discarded = False
            if anomaly is not None:
                discarded = self._handle_anomaly(anomaly, program, scope,
                                                 checkpoint, iters=iters)
            else:
                self._anomaly_skips = 0
            if not discarded:
                scope.set_var(RNG_STATE_VAR, new_rng)
                for n, v in new_state.items():
                    scope.set_var(n, v)
            # the step's arguments were the last holders of the donated
            # arrays: released here, inside the span and the run's wall
            # time, not in the caller's frame as this one is torn down
            p.state = p.rng = None

            if p.save_ops and not discarded:
                # TPU deviation from save_op.cc (which executes at its
                # program-order position): the whole block runs as ONE
                # compiled step, so saves always record the POST-step
                # committed value (after step k of a window: ONE write
                # per save op), and only persistable (scope-held) vars
                # are saveable. One PTC1 entry per file — exactly what
                # layers.load reads back.
                from .core import tensor_io

                for name, path in p.save_ops:
                    val = scope.find_var(name)
                    if val is None:
                        raise RuntimeError(
                            "save op: var %r is not in the scope — only "
                            "PERSISTABLE vars can be saved (the step "
                            "commits those; intermediates are fused away "
                            "by XLA). fetch_list the value instead."
                            % name)
                    os.makedirs(os.path.dirname(path) or ".",
                                exist_ok=True)
                    tensor_io.save_combine(path,
                                           {name: _fetch_numpy(val)})

            if checkpoint is not None and not discarded:
                checkpoint[0].step_completed(program, scope, iters,
                                             checkpoint[1])

            # a preemption signal that landed DURING the step (or window)
            # drains now, after the state committed — never torn in half
            _preemption.check_drain(checkpoint[0] if checkpoint else None,
                                    program, scope)

        wall = time.perf_counter() - t_run0
        _M_RUN_SECONDS.observe(wall)
        _M_RUNS.inc()
        if batched:
            _M_BATCHED_RUNS.inc()
            _M_BATCHED_ITERS.inc(iters)
        if _RUN_HOOKS:
            record = {
                "program_id": program._uid,
                "fetch_names": list(fetch_names),
                "wall_time": wall,
                "cache_hit": cache_hit,
                "profiler_enabled": profiling,
            }
            # omit-when-default: a single-step, sync record keeps its
            # exact key set (read record.get("iters", 1) / .get("async"))
            if batched:
                record["iters"] = iters
            if fetch_mode == "async":
                record["async"] = True
            _fire_run_hooks(record)

        if fetch_mode == "async":
            return [FetchHandle(x, name=n)
                    for n, x in zip(fetch_names, fetches)]
        if return_numpy:
            with _prof.RecordEvent(_prof.SPAN_FETCH):
                return [_fetch_numpy(x) for x in fetches]
        return list(fetches)

    # ------------------------------------------------------------------
    def _build(self, program, block, feeds, feed_sig, fetch_names,
               state_names, strategy, iters):
        """Compile the block's step: itself at ``iters == 1``, else a
        scan of it. The initial state is donated, so a step or a whole
        k-step window is allocation-free on device."""
        import jax

        from . import flags as _flags

        fn = _trace_step(block, fetch_names, strategy,
                         fixed_state=iters > 1)
        label = "step#%s" % ",".join(fetch_names[:3])
        if iters > 1:
            fn, label = _scan_steps(fn, iters), "batched#k=%d" % iters

        # skip_step/rollback re-commit the PRE-step scope arrays after a
        # discarded step or window; donation would have handed those
        # buffers to XLA (a no-op on CPU but fatal on TPU), so those
        # policies compile undonated, as inference-path executors do. The
        # policy sits in the compile-cache key, so flipping
        # FLAGS_anomaly_policy recompiles rather than reusing a
        # mismatched executable; the bit joins the disk key too.
        donate = ((0,) if self._donate_state
                  and _flags.anomaly_policy() == "raise" else ())
        cache_key = None
        if _compile_cache.active(self._cache_read_dirs):
            cache_key = _compile_cache.step_key(
                program, feed_sig, fetch_names, state_names, strategy,
                iters, bool(donate))

        # Startup-style programs create new persistables -> output structure
        # depends on trace; jit handles that fine since structure is fixed
        # per cache entry.
        if strategy is not None and strategy.mesh is not None:
            if iters == 1:
                jfn = strategy.wrap_step(
                    fn, program, block, feeds[0], fetch_names,
                    state_names, cache_key=cache_key,
                    cache_read_dirs=self._cache_read_dirs)
            else:
                jfn = strategy.wrap_batched_step(
                    fn, block, *feeds, fetch_names, state_names,
                    cache_key=cache_key,
                    cache_read_dirs=self._cache_read_dirs,
                    program=program, iters=iters)
        else:
            jfn = _compile_cache.wrap_jit(
                jax.jit(fn, donate_argnums=donate), cache_key,
                read_dirs=self._cache_read_dirs, label=label)
        return _CompiledStep(jfn, state_names, fetch_names)

    # convenience ------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """One pass over ``dataset`` (reference ``executor.py:920`` +
        trainer/DeviceWorker stack). The reference spawns per-thread C++
        workers over dataset channels; here each batch runs through the
        same compile-cached XLA step ``run()`` uses — thread-level
        parallelism lives in the dataset's parsing/prefetch side, device
        parallelism in the compiled step's shardings."""
        if dataset is None:
            raise ValueError("dataset is required")
        if thread:
            dataset.set_thread(thread)
        fetch_list = list(fetch_list or [])
        fetch_info = list(fetch_info or
                          [getattr(v, "name", str(v)) for v in fetch_list])
        n_batches = 0
        # double-buffer ahead-dispatch (the fluid/reader.py staging trick;
        # reference buffered_reader.h ReadAsync semantics): step i is
        # dispatched asynchronously (return_numpy=False keeps it
        # in-flight), then a background DeviceStager parses batch i+1 on
        # host and stages it H2D while the device executes — host prep
        # and device step overlap. A CompiledProgram's GSPMD feed
        # sharding is applied AT the stage, so data-parallel feeds land
        # pre-sharded across the mesh instead of funneling through
        # device 0.
        import numpy as _np

        from . import compiler as _compiler
        from .reader import DeviceStager, _as_sharding_fn, stage_feed

        sharding_fn = None
        if isinstance(program, _compiler.CompiledProgram) and \
                program.mesh is not None:
            sharding_fn = _as_sharding_fn(program)

        stager = DeviceStager(
            dataset.batch_reader()(),
            transform=lambda feed: stage_feed(feed, sharding_fn),
            capacity=2, name="dataset")
        try:
            for staged in stager:
                res = self.run(program, feed=staged,
                               fetch_list=fetch_list, scope=scope,
                               return_numpy=False)
                n_batches += 1
                if debug and fetch_list and n_batches % print_period == 0:
                    msg = ", ".join(
                        "%s=%s" % (info, _np.asarray(val).ravel()[:4])
                        for info, val in zip(fetch_info, res))
                    print("batch %d: %s" % (n_batches, msg))
        finally:
            stager.close()
        return n_batches

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Reference ``executor.py:847``: identical drive, inference
        program (no optimizer ops — the program decides, not the call)."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    def as_function(self, program, feed_specs, fetch_list, scope=None):
        """Exposes a Program block as a pure jittable function
        ``fn(state_dict, feed_dict, rng_key) -> (fetches, new_state, key)``
        plus example args. ``feed_specs``: {name: example ndarray}."""
        scope = scope or global_scope()
        fetch_names = [v.name if isinstance(v, Variable) else str(v) for v in fetch_list]
        state_names = _state_names(program, scope)
        step = _trace_step(program.global_block(), fetch_names)
        state = {n: scope.find_var(n) for n in state_names}
        rng = scope.find_var(RNG_STATE_VAR)
        if rng is None:
            rng = _rng.key_data(_rng.root_key(program.random_seed or 0))
        return step, (state, dict(feed_specs), rng)

    def close(self):
        """Release compiled steps and reap any in-flight window
        prefetch (joining its non-daemon thread; already-pulled batches
        of an abandoned pass are dropped)."""
        pending = list(self._window_prefetch.values())
        self._window_prefetch.clear()
        for pf in pending:
            pf.discard()
        self._cache.clear()


def _as_lodtensor(data, place=None):
    return np.asarray(data)
