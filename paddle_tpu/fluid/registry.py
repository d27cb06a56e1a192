"""Op registry: op type -> lowering rule.

Plays the role of the reference's ``OpInfoMap`` + ``REGISTER_OPERATOR``
(``paddle/fluid/framework/op_registry.h:199``, ``op_info.h:115``) but instead
of per-device kernel dispatch, each op has a single *lowering rule* that emits
JAX/XLA (or Pallas) computation when a Block is traced into one compiled
function. This is the TPU-native analogue of the kernel layer: XLA does the
tiling/fusion that per-op CUDA kernels hand-coded.

A lowering rule has signature ``lower(ctx, op)`` where ``ctx`` is a
``LowerCtx`` giving read/write access to the symbolic environment, and ``op``
is the ``framework.Operator``. Rules read inputs with ``ctx.get`` and bind
outputs with ``ctx.set``.
"""

import re

import numpy as np


class OpInfo:
    def __init__(self, type, lower, has_state=False):
        self.type = type
        self.lower = lower
        # has_state: op reads/advances the RNG stream (dropout, random init)
        self.has_state = has_state


class OpRegistry:
    def __init__(self):
        self._ops = {}

    def register(self, type, lower=None, **kw):
        if lower is None:  # decorator form
            def deco(fn):
                self._ops[type] = OpInfo(type, fn, **kw)
                return fn

            return deco
        self._ops[type] = OpInfo(type, lower, **kw)
        return lower

    def get(self, type):
        info = self._ops.get(type)
        if info is None:
            raise NotImplementedError(
                "Op %r has no lowering rule registered (see paddle_tpu/fluid/ops/)" % type
            )
        return info

    def has(self, type):
        return type in self._ops

    def types(self):
        return sorted(self._ops)


registry = OpRegistry()
register = registry.register


class LowerCtx:
    """Symbolic environment threaded through a block lowering.

    - ``env``: name -> jax value (tracers during jit trace).
    - ``written``: persistable names assigned during the trace (optimizer
      updates, BN running stats, step counters) — the executor commits these
      back to the Scope, the analogue of the reference's in-place scope
      mutation under XLA's functional model.
    - RNG: a single threaded PRNG key. Each stateful op calls ``next_rng``.
      During autodiff replay (``replay_keys``) the recorded keys are reused so
      the recomputed forward matches bit-for-bit (reference analogue: fixed
      dropout masks saved for backward).
    """

    def __init__(self, block, env, rng_key, mesh=None, replay_keys=None):
        self.block = block
        self.program = block.program
        self.env = env
        self.rng_key = rng_key
        self.mesh = mesh
        self.used_keys = []
        self._replay_keys = list(replay_keys) if replay_keys is not None else None
        self.written = set()
        # per-op [start, end) spans into used_keys, recorded by lower_block —
        # the autodiff recompute path slices keys per checkpoint segment
        self.op_key_spans = {}
        # snapshots for autodiff replay (see ops/autodiff.py)
        self.initial_env = dict(env)
        self.initial_rng = rng_key

    def get(self, name):
        if name not in self.env:
            raise KeyError(
                "Var %r not materialized; it must be fed, persistable, or "
                "produced by an earlier op" % name
            )
        return self.env[name]

    def get_input(self, op, slot, default=None):
        names = op.input(slot)
        if not names:
            return default
        return self.get(names[0])

    def get_inputs(self, op, slot):
        return [self.get(n) for n in op.input(slot)]

    def set(self, name, value):
        self.env[name] = value
        v = self.block._find_var_recursive(name)
        if v is not None and v.persistable:
            self.written.add(name)

    def set_output(self, op, slot, value):
        names = op.output(slot)
        if names:
            self.set(names[0], value)

    def var(self, name):
        return self.block._find_var_recursive(name)

    def next_rng(self):
        import jax

        if self._replay_keys is not None:
            key = self._replay_keys.pop(0)
        else:
            self.rng_key, key = jax.random.split(self.rng_key)
        self.used_keys.append(key)
        return key

    def var_dtype(self, name):
        v = self.var(name)
        return np.dtype(v.dtype) if v is not None else np.dtype("float32")


def propagate_lod(ctx, op):
    """Dataflow LoD propagation: if exactly one input carries an @LOD
    lengths binding and an output has the same (static) token dimension,
    the output inherits it — the analogue of the reference's ShareLoD in
    per-op InferShape, done generically on the lowered values."""
    in_lods = []
    for name in op.input_arg_names():
        key = name + "@LOD"
        if key in ctx.env and name in ctx.env:
            in_lods.append((name, ctx.env[key]))
    if not in_lods:
        return
    # several LoD inputs (e.g. concat along features) may share one
    # segmentation. Propagate the first input's lengths only when every
    # LoD input agrees on sequence count and token dim — values can't be
    # compared at trace time; like the reference's ShareLoD, equal-shape
    # disagreement is the caller's contract violation. Disagreeing shapes
    # propagate nothing, so downstream sequence ops raise loudly.
    first_len = in_lods[0][1]
    leads = set()
    for name, lv in in_lods:
        v = ctx.env[name]
        if not np.ndim(v):
            return
        leads.add(np.shape(v)[0])
        if np.shape(lv) != np.shape(first_len):
            return
    if len(leads) != 1:
        return
    lengths = first_len
    lead = leads.pop()
    for out in op.output_arg_names():
        key = out + "@LOD"
        if key in ctx.env or out not in ctx.env:
            continue
        v = ctx.env[out]
        if np.ndim(v) and np.shape(v)[0] == lead:
            ctx.env[key] = lengths


class EnforceError(RuntimeError):
    """Op-attributed error (reference PADDLE_ENFORCE + op_call_stack.cc):
    carries which op failed and where user code created it."""


def attribute_op_error(op, exc):
    """Re-raise ``exc`` wrapped with the op's identity + creation site."""
    lines = ["op %r failed during lowering: %s: %s"
             % (op.type, type(exc).__name__, exc)]
    ins = {k: v for k, v in op.inputs.items() if v}
    outs = {k: v for k, v in op.outputs.items() if v}
    lines.append("  inputs: %r  outputs: %r" % (ins, outs))
    stack = getattr(op, "callstack", None)
    if stack:
        lines.append("  created at (most recent user frame first):")
        lines.extend("    " + s for s in stack)
    raise EnforceError("\n".join(lines)) from exc


# Op types whose lowering actually ran in this process — the
# execution-based coverage gate (tests/test_zz_coverage_gate.py) asserts
# every registered type lands here during the full suite, so a lowering
# that is merely *mentioned* in test text can no longer pass the gate.
EXECUTED_OP_TYPES = set()


# the model's layer tag on a variable's name (models/bert.py:
# ``layer_3_attn_q``)
LAYER_TAG = re.compile(r"^layer_\d+_")


def op_scope(op):
    """The ``jax.named_scope`` an op is lowered under: its type, behind
    the layer tag where its first output carries one (``layer_3_mul``).
    Every HLO operation's ``op_name`` then says which program op it came
    from, ``transpose(jvp(layer_3_mul))`` for its backward under the
    ``autodiff`` op's replay; ``profiler.region_of`` reads it back.
    Trace-time only."""
    outs = op.output_arg_names()
    tag = LAYER_TAG.match(outs[0]) if outs else None
    return (tag.group(0) if tag else "") + op.type


# the scope a model module's ops are lowered under, outside ``op_scope``:
# ``module.mtp`` for an op built inside ``fluid.name_scope("mtp")``
MODULE_SCOPE = "module."


def module_scope(op):
    """The ``jax.named_scope`` that says which model module an op belongs
    to - ``module.<name>`` from the ``fluid.name_scope`` it was built in
    (its ``op_namescope``; nested scopes joined by ``.``) - or None for an
    op built outside any. ``profiler.module_of`` reads it back, forward,
    replay and backward alike. Trace-time only."""
    scope = op.attrs.get("op_namescope")
    return MODULE_SCOPE + scope.replace("/", ".") if scope else None


def lower_op(ctx, op):
    """Lower ONE op with error attribution + LoD propagation — the single
    entry every lowering loop (block, sub-block, replay, pipeline stage)
    must use so failures name the failing op and its creation site."""
    import contextlib

    import jax

    EXECUTED_OP_TYPES.add(op.type)
    module = module_scope(op)
    try:
        with (jax.named_scope(module) if module
              else contextlib.nullcontext()), \
                jax.named_scope(op_scope(op)):
            registry.get(op.type).lower(ctx, op)
    except EnforceError:
        raise
    except Exception as e:  # noqa: B902 — attribute, then re-raise
        attribute_op_error(op, e)
    propagate_lod(ctx, op)


def lower_block(ctx, block):
    """Run every op's lowering rule in order (the `Executor::RunPreparedContext`
    hot-loop analogue, reference executor.cc:411 — but traced once, compiled
    by XLA, not interpreted per step)."""
    for op in block.ops:
        start = len(ctx.used_keys)
        lower_op(ctx, op)
        ctx.op_key_spans[id(op)] = (start, len(ctx.used_keys))
