"""Core graph-building IR: Program / Block / Operator / Variable / Parameter.

Capability parity with the reference's ``python/paddle/fluid/framework.py``
(Variable:561, Operator:1680, Block:2132, Program:3515) and the C++ desc
layer (``paddle/fluid/framework/program_desc.h:30``), re-designed TPU-first:

* The IR is a declarative program of named ops over named vars — the same
  exchange-format role ``ProgramDesc`` plays — but there is no per-op C++
  kernel dispatch. Whole blocks are lowered to a single pure JAX function
  and compiled by XLA (see ``executor.py``).
* Shape inference runs through ``jax.eval_shape`` on each op's lowering rule
  (single source of truth), instead of hand-written InferShape per op.
* Serialization is protobuf-backed (``core/framework_pb2``), mirroring the
  reference's on-disk capability.
"""

import contextlib
import copy
import itertools

import numpy as np

from . import unique_name


_name_scope_stack = []


@contextlib.contextmanager
def name_scope(prefix=None):
    """Reference ``fluid.name_scope``: op grouping recorded as the
    ``op_namescope`` attr (what the reference's graph viewer groups by)
    and carried onto every HLO operation the op lowers to, so a device
    trace can be read by model module (``profiler.module_of``); no effect
    on what is computed."""
    if prefix:
        _name_scope_stack.append(str(prefix))
    try:
        yield
    finally:
        if prefix:
            _name_scope_stack.pop()


def _program_version():
    from .compat import PROGRAM_VERSION

    return PROGRAM_VERSION

# ---------------------------------------------------------------------------
# dtype handling
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float32": np.dtype("float32"),
    "float64": np.dtype("float64"),
    "float16": np.dtype("float16"),
    "bfloat16": None,  # filled lazily to avoid importing jax at module load
    "int8": np.dtype("int8"),
    "uint8": np.dtype("uint8"),
    "int16": np.dtype("int16"),
    "int32": np.dtype("int32"),
    "int64": np.dtype("int64"),
    "bool": np.dtype("bool"),
}


def convert_dtype(dtype):
    """Normalize a dtype spec (str / np.dtype / jnp dtype) to np.dtype."""
    if dtype is None:
        return np.dtype("float32")
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            import ml_dtypes

            return np.dtype(ml_dtypes.bfloat16)
        if dtype in _DTYPE_ALIASES and _DTYPE_ALIASES[dtype] is not None:
            return _DTYPE_ALIASES[dtype]
    return np.dtype(dtype)


def dtype_str(dtype):
    return np.dtype(dtype).name


# ---------------------------------------------------------------------------
# Variable
# ---------------------------------------------------------------------------


class Variable:
    """A named tensor slot in a Block (reference ``framework.py:561``).

    Holds static metadata only; at run time the value lives in a Scope as a
    device-resident ``jax.Array``. ``shape`` may contain -1 for deferred
    (batch) dimensions.
    """

    def __init__(
        self,
        block,
        name=None,
        shape=None,
        dtype="float32",
        persistable=False,
        stop_gradient=False,
        is_data=False,
        initializer=None,
        type="lod_tensor",
        lod_level=0,
    ):
        self.block = block
        self.name = name or unique_name.generate("_generated_var")
        self.shape = tuple(shape) if shape is not None else ()
        self.dtype = convert_dtype(dtype)
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.lod_level = lod_level
        # "lod_tensor" (dense) or "selected_rows" (sparse rows+values pair;
        # a selected_rows var NAME binds the values array in the env and
        # NAME + "@ROWS" binds the int32 row-index array — the TPU-native
        # encoding of reference SelectedRows, selected_rows.h:32)
        self.type = type
        self.op = None  # producing op, set by append_op

    # -- python operator sugar (maps to ops, usable while building graphs) --
    def _binary(self, other, op_type, reverse=False):
        from .layers import math_op_patch

        return math_op_patch.binary_op(self, other, op_type, reverse)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    def __radd__(self, other):
        return self._binary(other, "elementwise_add", True)

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        return self._binary(other, "elementwise_sub", True)

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    def __rmul__(self, other):
        return self._binary(other, "elementwise_mul", True)

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __rtruediv__(self, other):
        return self._binary(other, "elementwise_div", True)

    def __pow__(self, other):
        return self._binary(other, "elementwise_pow")

    def __neg__(self):
        from .layers.nn import scale as _scale

        return _scale(self, scale=-1.0)

    def __lt__(self, other):
        return self._binary(other, "less_than")

    def __le__(self, other):
        return self._binary(other, "less_equal")

    def __gt__(self, other):
        return self._binary(other, "greater_than")

    def __ge__(self, other):
        return self._binary(other, "greater_equal")

    @property
    def grad_name(self):
        return grad_var_name(self.name)

    def astype(self, dtype):
        from .layers.tensor import cast

        return cast(self, dtype)

    def __repr__(self):
        return "Variable(name=%s, shape=%s, dtype=%s%s)" % (
            self.name,
            self.shape,
            dtype_str(self.dtype),
            ", persistable" if self.persistable else "",
        )

    def to_desc(self):
        return {
            "name": self.name,
            "shape": list(self.shape),
            "dtype": dtype_str(self.dtype),
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "is_data": self.is_data,
            "is_parameter": isinstance(self, Parameter),
            "trainable": getattr(self, "trainable", False),
        }


class Parameter(Variable):
    """A trainable persistable Variable (reference ``framework.py:4459``)."""

    def __init__(self, block, shape, dtype, name=None, trainable=True,
                 regularizer=None, initializer=None, do_model_average=False,
                 learning_rate=1.0):
        super().__init__(
            block,
            name=name,
            shape=shape,
            dtype=dtype,
            persistable=True,
            stop_gradient=not trainable,
        )
        self.trainable = trainable
        self.regularizer = regularizer
        self.initializer = initializer
        self.do_model_average = do_model_average
        self.optimize_attr = {"learning_rate": learning_rate}


GRAD_SUFFIX = "@GRAD"


def grad_var_name(name):
    return name + GRAD_SUFFIX


_PKG_DIR = None


def _user_callsite(max_frames=3):
    """File:line of the nearest frames OUTSIDE paddle_tpu — the user's layer
    call site (cheap: walks raw frames, no traceback formatting)."""
    global _PKG_DIR
    if _PKG_DIR is None:
        import os
        import sys  # noqa: F401

        _PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys

    frames = []
    f = sys._getframe(2)
    while f is not None and len(frames) < max_frames:
        fn = f.f_code.co_filename
        if not fn.startswith(_PKG_DIR):
            frames.append("%s:%d in %s" % (fn, f.f_lineno, f.f_code.co_name))
        f = f.f_back
    return frames


def record_op_callstacks(enabled=True):
    """Toggle op call-site recording (on by default; tiny per-op cost at
    graph-build time only)."""
    Operator._record_callstacks = bool(enabled)


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------


class Operator:
    """One IR op: type + named input/output var lists + attrs.

    Mirrors the reference ``OpDesc`` (``framework.proto:43``); execution-time
    semantics come from the op registry's lowering rule (``registry.py``).
    """

    # op-attributed errors (reference framework/op_call_stack.cc): each op
    # records where user code created it, so lowering/runtime failures can
    # name the layer call site. Toggle via record_op_callstacks().
    _record_callstacks = True

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        # canonical form: {slot: [var_name, ...]}
        self.inputs = {k: _as_name_list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: _as_name_list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})
        self.callstack = _user_callsite() if Operator._record_callstacks \
            else None

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    def input_arg_names(self):
        return [n for vs in self.inputs.values() for n in vs]

    def output_arg_names(self):
        return [n for vs in self.outputs.values() for n in vs]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def __repr__(self):
        return "{%s: (%s) -> (%s)}" % (
            self.type,
            ", ".join("%s=%s" % kv for kv in self.inputs.items()),
            ", ".join("%s=%s" % kv for kv in self.outputs.items()),
        )

    def to_desc(self):
        return {
            "type": self.type,
            "inputs": {k: list(v) for k, v in self.inputs.items()},
            "outputs": {k: list(v) for k, v in self.outputs.items()},
            "attrs": _sanitize_attrs(self.attrs),
        }


def _as_name_list(v):
    if v is None:
        return []
    if isinstance(v, (list, tuple)):
        return [_as_name(x) for x in v]
    return [_as_name(v)]


def _as_name(v):
    return v.name if isinstance(v, Variable) else str(v)


def _sanitize_attrs(attrs):
    out = {}
    for k, v in attrs.items():
        if isinstance(v, np.ndarray):
            out[k] = v.tolist()
        elif isinstance(v, np.generic):
            out[k] = v.item()
        elif isinstance(v, Variable):
            out[k] = v.name
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


class Block:
    """An ordered op list + var table (reference ``framework.py:2132``)."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("Variable %r not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def create_var(self, **kwargs):
        name = kwargs.get("name")
        if name and name in self.vars:
            return self.vars[name]
        v = Variable(self, **kwargs)
        self.vars[v.name] = v
        self.program._bump()
        return v

    def create_parameter(self, **kwargs):
        p = Parameter(self, **kwargs)
        # parameters live in the enclosing (global) block's var table
        gb = self.program.global_block()
        gb.vars[p.name] = p
        self.program._bump()
        return p

    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        if _name_scope_stack:
            # the module an op belongs to (reference op_desc
            # "op_namescope"): no lowering reads it; lower_op puts it on
            # the op's HLO op_name (registry.module_scope)
            op.attrs.setdefault("op_namescope",
                                "/".join(_name_scope_stack))
        self.ops.append(op)
        for name in op.output_arg_names():
            v = self._find_var_recursive(name)
            if v is not None and v.op is None:
                v.op = op
        self.program._bump()
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self.program._bump()
        return op

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def to_desc(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_desc() for v in self.vars.values()],
            "ops": [op.to_desc() for op in self.ops],
        }


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------


class Program:
    """A multi-block IR program (reference ``framework.py:3515``).

    ``_mutation`` is a monotonically increasing edit counter used by the
    Executor's compile cache to detect graph changes cheaply.
    """

    _uid_counter = itertools.count()

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._mutation = 0
        self._seed_counter = 0
        # unique per-Program token: the Executor cache key must not use
        # id(program) — a GC'd Program's id can be reused and serve a stale
        # compiled step
        self._uid = next(Program._uid_counter)
        # set by append_backward: maps param name -> grad var name
        self.param_grad_map = {}

    def _bump(self):
        self._mutation += 1

    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def block(self, idx):
        return self.blocks[idx]

    @property
    def num_blocks(self):
        return len(self.blocks)

    def _create_block(self, parent_idx=None):
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump()
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def all_parameters(self):
        return self.global_block().all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            yield from blk.vars.values()

    def clone(self, for_test=False):
        """Deep-copies the IR. ``for_test=True`` switches ops to eval mode
        (dropout off, batch_norm uses running stats) like the reference's
        ``Program.clone(for_test=True)``."""
        p = Program.__new__(Program)
        p._uid = next(Program._uid_counter)
        p.random_seed = self.random_seed
        p._mutation = 0
        p._seed_counter = self._seed_counter
        p.param_grad_map = dict(self.param_grad_map)
        p.current_block_idx = 0
        p.blocks = []
        for blk in self.blocks:
            nb = Block(p, blk.idx, blk.parent_idx)
            for v in blk.vars.values():
                nv = copy.copy(v)
                nv.block = nb
                nv.op = None
                nb.vars[nv.name] = nv
            for op in blk.ops:
                attrs = dict(op.attrs)
                if for_test and attrs.get("is_test") is False:
                    attrs["is_test"] = True
                nb.ops.append(Operator(nb, op.type, op.inputs, op.outputs, attrs))
            p.blocks.append(nb)
        return p

    def _prune(self, targets):
        """Keeps only ops needed to compute ``targets`` (reference prune.h).

        Returns a cloned pruned Program. Persistable writes (optimizer
        updates) are dropped unless needed — this is what
        ``save_inference_model`` uses.

        Control-flow ops (while/cond/...) declare only part of their
        data flow as explicit inputs/outputs; the rest rides their
        sub-blocks (a branch reads a parent-block fc output, a while
        body writes an array the tail reads). Reverse reachability
        therefore matches against each op's TRANSITIVE reads/writes —
        explicit args plus every nested sub-block op's args (reference
        prune.h walks sub-block descs the same way). Sub-block-internal
        names never collide into block 0 (unique-name generation), so
        the widening only ever keeps more, never less.
        """
        target_names = set(_as_name_list(targets))
        p = self.clone(for_test=True)
        blk = p.global_block()

        def _transitive_args(op):
            reads = set(op.input_arg_names())
            writes = set(op.output_arg_names())
            seen, stack = set(), [op]
            while stack:
                for key, val in stack.pop().attrs.items():
                    if key == "sub_block" or key.endswith("_block"):
                        idxs = [val] if isinstance(val, int) else []
                    elif key == "blocks" and isinstance(val, (list, tuple)):
                        idxs = [v for v in val if isinstance(v, int)]
                    else:
                        continue
                    for idx in idxs:
                        if 0 <= idx < len(p.blocks) and idx not in seen:
                            seen.add(idx)
                            for sub_op in p.blocks[idx].ops:
                                reads.update(sub_op.input_arg_names())
                                writes.update(sub_op.output_arg_names())
                                stack.append(sub_op)
            return reads, writes

        needed = set(target_names)
        kept = []
        for op in reversed(blk.ops):
            reads, writes = _transitive_args(op)
            if writes & needed:
                kept.append(op)
                needed.update(reads)
        blk.ops = list(reversed(kept))
        return p

    # -- serialization ------------------------------------------------------
    def to_desc(self):
        return {
            "version": _program_version(),
            "random_seed": self.random_seed,
            "blocks": [b.to_desc() for b in self.blocks],
            "param_grad_map": dict(self.param_grad_map),
        }

    def serialize_to_string(self):
        from .core import proto_io

        return proto_io.program_to_bytes(self.to_desc())

    @staticmethod
    def parse_from_string(data):
        from .core import proto_io

        return Program.from_desc(proto_io.program_from_bytes(data))

    @staticmethod
    def from_desc(desc):
        p = Program.__new__(Program)
        p._uid = next(Program._uid_counter)
        p.random_seed = desc.get("random_seed", 0)
        p._mutation = 0
        p._seed_counter = 0
        p.param_grad_map = dict(desc.get("param_grad_map", {}))
        p.current_block_idx = 0
        p.blocks = []
        for bdesc in desc["blocks"]:
            blk = Block(p, bdesc["idx"], bdesc.get("parent_idx", -1))
            for vdesc in bdesc["vars"]:
                if vdesc.get("is_parameter"):
                    v = Parameter(
                        blk,
                        shape=vdesc["shape"],
                        dtype=vdesc["dtype"],
                        name=vdesc["name"],
                        trainable=vdesc.get("trainable", True),
                    )
                else:
                    v = Variable(
                        blk,
                        name=vdesc["name"],
                        shape=vdesc["shape"],
                        dtype=vdesc["dtype"],
                        persistable=vdesc.get("persistable", False),
                        stop_gradient=vdesc.get("stop_gradient", False),
                        is_data=vdesc.get("is_data", False),
                    )
                blk.vars[v.name] = v
            for odesc in bdesc["ops"]:
                blk.ops.append(
                    Operator(blk, odesc["type"], odesc["inputs"],
                             odesc["outputs"], odesc["attrs"])
                )
            p.blocks.append(blk)
        return p

    def __repr__(self):
        lines = []
        for blk in self.blocks:
            lines.append("block %d (parent %d):" % (blk.idx, blk.parent_idx))
            for op in blk.ops:
                lines.append("  " + repr(op))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# default programs / guards (reference framework.py:4559,4593)
# ---------------------------------------------------------------------------

_main_program = Program()
_startup_program = Program()


def default_main_program():
    return _main_program


def default_startup_program():
    return _startup_program


def switch_main_program(program):
    global _main_program
    old = _main_program
    _main_program = program
    return old


def switch_startup_program(program):
    global _startup_program
    old = _startup_program
    _startup_program = program
    return old


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


# -- dygraph mode switch (populated by dygraph package) ---------------------

_dygraph_tracer_ = None


def _dygraph_tracer():
    return _dygraph_tracer_


def in_dygraph_mode():
    return _dygraph_tracer_ is not None


@contextlib.contextmanager
def _dygraph_guard(tracer):
    global _dygraph_tracer_
    old = _dygraph_tracer_
    _dygraph_tracer_ = tracer
    try:
        yield
    finally:
        _dygraph_tracer_ = old
