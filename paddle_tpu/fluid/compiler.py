"""CompiledProgram: attaches a parallel-execution strategy to a Program.

Parity: reference ``python/paddle/fluid/compiler.py:65`` — but where the
reference's ``with_data_parallel`` builds per-device SSA graphs with inserted
NCCL allreduce ops (``multi_devices_graph_pass.cc``), here the SAME lowered
step function is jit-compiled under a ``jax.sharding.Mesh`` with GSPMD
shardings: the batch is sharded over the 'dp' axis, parameters are
replicated, and XLA inserts the gradient all-reduces over ICI automatically.
BuildStrategy/ExecutionStrategy survive as config surface.
"""

import itertools

import numpy as np

from . import compile_cache as _compile_cache
from . import monitor as _monitor
from . import rng as _rng

__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy",
           "UnsupportedStrategyError", "RESERVED_AXES",
           "pipeline_segments"]

_M_RESHARD_REPL = _monitor.counter(
    "state_reshard_replicated_total",
    help="state vars whose shard spec could not be applied on the "
         "current mesh (axis gone or dim not divisible after an "
         "elastic reformation) and fell back to replicated")

_M_PIPE_BUBBLE = _monitor.gauge(
    "pipeline_bubble_fraction",
    help="analytic GPipe bubble fraction (S-1)/(M+S-1) of the most "
         "recently compiled pipeline schedule")

_M_PIPE_MB = _monitor.counter(
    "pipeline_microbatches_total",
    help="microbatches pushed through the pipeline schedule (M per "
         "step, M*k per iters=k window)")

_M_TP_BYTES = _monitor.counter(
    "tp_collective_bytes_total",
    help="analytic bytes moved by the model-axis collectives the "
         "pipeline TP plan inserted (forward psum of each row-parallel "
         "output + backward psum of each column-parallel input, per "
         "microbatch) — an estimate from static shapes, not a NIC "
         "counter")


# Axis names with a fixed role in the 4-axis topology. A user-supplied
# mesh axis may only use one of these names where the strategy actually
# implements that role — otherwise e.g. a "stage" data-parallel axis
# would silently shadow the pipeline schedule's axis.
RESERVED_AXES = frozenset({"host", "stage", "model", "data", "sp"})


class UnsupportedStrategyError(RuntimeError):
    """A CompiledProgram strategy was asked to run in a mode it refuses
    (e.g. ``iters=k`` step batching under ``with_explicit_collectives``).
    Subclasses RuntimeError so pre-existing callers that caught the old
    bare RuntimeError keep working."""


def _validate_mesh_axes(axes, honored, mode, require=()):
    """Reserved-name policy for user-supplied mesh axes: every axis in
    ``RESERVED_AXES`` carries a fixed role, and is only accepted where
    ``mode`` implements that role (``honored``). ``require`` lists axes
    the mode cannot run without."""
    axes = tuple(axes)
    if len(set(axes)) != len(axes):
        raise ValueError("mesh axes %r contain duplicates" % (axes,))
    bad = sorted(a for a in axes if a in RESERVED_AXES and a not in honored)
    if bad:
        raise ValueError(
            "mesh axes %r are reserved names (reserved set: %s) whose "
            "role %s does not implement — it honors %s; rename the axis "
            "or use the strategy that owns it"
            % (bad, sorted(RESERVED_AXES), mode, sorted(honored)))
    missing = [a for a in require if a not in axes]
    if missing:
        raise ValueError(
            "%s requires mesh axes %r; got %r" % (mode, missing, axes))
    return axes


def pipeline_segments(program, block):
    """Split the block's forward ops at the recorded pipeline cuts.

    Returns ``(segments, cut_groups, ad_idx)``: one op-list per stage,
    one tuple of var names per boundary (the activation bundle that
    hops stage r -> r+1 — ``PipelineOptimizer(cut_list=...)`` entries
    that were lists/tuples become multi-var bundles), and the index of
    the ``autodiff`` op (None for a forward-only program). Shared with
    ``tools/stagebalance.py`` so the CLI audits the exact segmentation
    the compiled schedule will run."""
    ops = list(block.ops)
    ad_idx = next((i for i, o in enumerate(ops) if o.type == "autodiff"),
                  None)
    fwd_ops = ops[:ad_idx] if ad_idx is not None else ops
    cut_groups = [tuple(names) for names in
                  getattr(program, "_pipeline_cut_vars", [])]
    producer = {}
    for i, o in enumerate(fwd_ops):
        for nm in o.output_arg_names():
            producer[nm] = i
    segments, start = [], 0
    for grp in cut_groups:
        missing = [n for n in grp if n not in producer]
        if missing:
            raise ValueError(
                "pipeline cut vars %r are not produced by any forward "
                "op" % (missing,))
        end = max(producer[n] for n in grp)
        if end < start:
            raise ValueError(
                "pipeline cut %r is ordered before the previous cut — "
                "cut_list must follow dataflow order" % (grp,))
        segments.append(fwd_ops[start:end + 1])
        start = end + 1
    segments.append(fwd_ops[start:])
    return segments, cut_groups, ad_idx


class _AttrProxy:
    """Present an op with some attrs overridden to a lowering rule —
    the per-shard pipeline TP path patches shape-carrying attrs
    (reshape targets, head counts) without mutating the shared IR."""

    def __init__(self, op, overrides):
        self._op = op
        self._overrides = overrides

    def attr(self, name, default=None):
        if name in self._overrides:
            return self._overrides[name]
        return self._op.attr(name, default)

    def __getattr__(self, name):
        return getattr(self._op, name)


class _ModelAxisPlan:
    """Static Megatron-TP plan for lowering a forward-op sequence on
    per-shard ``model``-axis values inside the (fully manual) pipeline
    shard_map.

    GSPMD does this propagation implicitly from ``ParamAttr(shard=...)``
    layouts; the pipeline schedule runs manual (ppermute over 'stage'
    crashes the partial-auto partitioner on this jaxlib), so the same
    information is derived here ahead of trace: which activation dims
    are sharded over 'model', where the two Megatron region collectives
    go (``copy_to_tp_region`` on each column-parallel input — identity
    forward, psum backward — and ``reduce_from_tp_region`` on each
    row-parallel output), and which shape/head attrs must be divided by
    the axis size for local-shard lowering.

    ``spec``: var name -> sharded dim index (absent = replicated).
    ``copy_in``/``reduce_out``: ids of matmul ops needing a region op.
    ``attr_override``: op id -> {attr: per-shard value}.
    ``psum_bytes``: analytic bytes one microbatch moves through the
    inserted collectives (fwd psums + bwd psums), feeding the
    ``tp_collective_bytes_total`` series.
    """

    _PASSTHROUGH = {"scale", "relu", "gelu", "tanh", "sigmoid", "cast",
                    "dropout", "assign", "square", "sqrt", "exp", "abs",
                    "clip", "leaky_relu"}
    _ELEMENTWISE = {"elementwise_add", "elementwise_sub",
                    "elementwise_mul", "elementwise_div",
                    "elementwise_max", "elementwise_min",
                    "elementwise_pow"}

    def __init__(self, block, fwd_ops, axis, size):
        self.axis = axis
        self.size = int(size)
        self.spec = {}
        self.copy_in = set()
        self.reduce_out = set()
        self.attr_override = {}
        self.psum_bytes = 0
        self._block = block
        for op in fwd_ops:
            self._visit(op)

    # -- helpers -------------------------------------------------------
    def _shape(self, name):
        v = self._block._find_var_recursive(name)
        return tuple(v.shape) if v is not None and v.shape else ()

    def _bytes(self, name):
        shape = self._shape(name)
        if not shape or any(d is None or d < 0 for d in shape):
            return 0
        v = self._block._find_var_recursive(name)
        itemsize = np.dtype(v.dtype).itemsize if v is not None else 4
        return int(np.prod(shape, dtype=np.int64)) * itemsize

    def _param_model_dim(self, name):
        v = self._block._find_var_recursive(name)
        pspec = getattr(v, "shard_spec", None) if v is not None else None
        if not pspec:
            return None
        dims = [d for d, a in enumerate(pspec) if a == self.axis]
        if not dims:
            return None
        if len(dims) > 1:
            raise ValueError(
                "param %r shard spec %r names the %r axis on more than "
                "one dim" % (name, pspec, self.axis))
        return dims[0]

    def _sdim(self, name):
        s = self.spec.get(name)
        if s is None:
            s = self._param_model_dim(name)
            if s is not None:
                self.spec[name] = s
        return s

    def _fail(self, op, why):
        raise ValueError(
            "model-axis propagation cannot lower op %r per-shard: %s. "
            "Either drop the ParamAttr shard spec feeding it or keep "
            "the 'model' axis out of this pipeline mesh." % (op.type, why))

    # -- per-op transfer rules -----------------------------------------
    def _visit(self, op):
        t = op.type
        if t in ("matmul", "mul"):
            return self._visit_matmul(op)
        if t in self._ELEMENTWISE:
            return self._visit_elementwise(op)
        if t in self._PASSTHROUGH:
            s = self._sdim(op.input("X")[0]) if op.input("X") else None
            if s is not None:
                for out in op.output_arg_names():
                    self.spec[out] = s
            return
        if t in ("reshape", "reshape2"):
            return self._visit_reshape(op)
        if t in ("transpose", "transpose2"):
            return self._visit_transpose(op)
        if t == "softmax":
            x = op.input("X")[0]
            s = self._sdim(x)
            if s is not None and s == len(self._shape(x)) - 1:
                self._fail(op, "softmax over the model-sharded dim")
            if s is not None:
                self.spec[op.output("Out")[0]] = s
            return
        if t == "sequence_parallel_attention":
            return self._visit_spa(op)
        if t == "layer_norm":
            if self._sdim(op.input("X")[0]) is not None:
                self._fail(op, "layer_norm over a model-sharded input "
                           "— place it outside the TP block")
            return
        if t == "lookup_table":
            if self._param_model_dim(op.input("W")[0]) is not None:
                self._fail(op, "vocab-parallel embedding is not "
                           "supported on the pipeline model axis (use "
                           "the GSPMD path)")
            return
        if t == "softmax_with_cross_entropy":
            if self._sdim(op.input("Logits")[0]) is not None:
                self._fail(op, "vocab-parallel cross entropy is not "
                           "supported — keep the projection un-sharded")
            return
        # default: refuse if anything sharded flows in; else no-op
        for name in op.input_arg_names():
            if self._sdim(name) is not None:
                self._fail(op, "input %r is sharded over %r and op %r "
                           "has no propagation rule"
                           % (name, self.axis, t))

    def _visit_matmul(self, op):
        xn, yn = op.input("X")[0], op.input("Y")[0]
        xs, ys = self._sdim(xn), self._sdim(yn)
        xr = len(self._shape(xn)) or 2
        yr = len(self._shape(yn)) or 2
        trans_y = bool(op.attr("transpose_Y", False))
        y_contract = yr - 1 if trans_y else yr - 2
        y_out = yr - 2 if trans_y else yr - 1
        out = op.output("Out")[0]
        out_rank = max(xr, yr)
        if xs is None and ys is None:
            return
        # both sharded on the same leading (batch/head) dim: a local
        # batched matmul, no collective (attention scores/context)
        if (xs is not None and ys == xs and xs < xr - 2 and xs < yr - 2):
            self.spec[out] = xs
            return
        if ys == y_out and xs is None and yr == 2:
            # column-parallel weight: activations come in replicated,
            # leave sharded on the output dim; cotangent needs the psum
            self.copy_in.add(id(op))
            self.spec[out] = out_rank - 1
            self.psum_bytes += self._bytes(xn)          # backward psum
            return
        if ys == y_contract and xs == xr - 1 and yr == 2:
            # row-parallel weight: sharded activations contract against
            # the weight's sharded input dim; psum the partial products
            self.reduce_out.add(id(op))
            self.psum_bytes += self._bytes(out)         # forward psum
            return
        if ys is None and xs is not None and xs < xr - 1 and yr == 2:
            self.spec[out] = xs
            return
        self._fail(op, "unsupported matmul sharding X[%s dim %s] @ "
                   "Y[%s dim %s]" % (xn, xs, yn, ys))

    def _visit_elementwise(self, op):
        xn, yn = op.input("X")[0], op.input("Y")[0]
        xs, ys = self._sdim(xn), self._sdim(yn)
        if xs is None and ys is None:
            return
        ax = op.attr("axis", -1)
        if ax not in (None, -1):
            self._fail(op, "sharded elementwise with explicit "
                       "broadcast axis %r" % ax)
        xshape, yshape = self._shape(xn), self._shape(yn)
        rx, ry = len(xshape), len(yshape)
        # trailing-aligned broadcast; out rank = max rank
        big_s, small_s = (xs, ys) if rx >= ry else (ys, xs)
        big_n, small_n = (xn, yn) if rx >= ry else (yn, xn)
        big_shape = xshape if rx >= ry else yshape
        small_shape = yshape if rx >= ry else xshape
        rb, rs = len(big_shape), len(small_shape)
        out = op.output("Out")[0]
        if big_s is not None:
            d_small = big_s - (rb - rs)
            if d_small >= 0:
                if small_s == d_small:
                    pass                        # both sharded, aligned
                elif small_s is None and small_shape[d_small] == 1:
                    pass                        # broadcasts over it
                else:
                    self._fail(op, "operand %r is full-size and "
                               "replicated on %r's sharded dim"
                               % (small_n, big_n))
            elif small_s is not None:
                self._fail(op, "operands sharded on incompatible dims")
            self.spec[out] = big_s
            return
        # only the smaller operand is sharded (a sharded bias onto a
        # replicated activation makes local shapes disagree)
        self._fail(op, "operand %r is sharded but %r is replicated "
                   "full-size" % (small_n, big_n))

    def _visit_reshape(self, op):
        xn = op.input("X")[0]
        s = self._sdim(xn)
        if s is None:
            return
        in_shape = self._shape(xn)
        target = list(op.attr("shape"))
        resolved = [in_shape[i] if d == 0 else d
                    for i, d in enumerate(target)]
        if any(d == -1 for d in resolved):
            numel = int(np.prod(in_shape, dtype=np.int64))
            known = int(np.prod([d for d in resolved if d != -1],
                                dtype=np.int64))
            resolved = [numel // known if d == -1 else d
                        for d in resolved]
        # maximal contiguous groups with equal products map input dims
        # to output dims; the sharded dim must lead its group so the
        # shard stays a contiguous block of the global tensor
        groups, i, j = [], 0, 0
        while i < len(in_shape) and j < len(resolved):
            gi, gj = [i], [j]
            pi, pj = in_shape[i], resolved[j]
            while pi != pj:
                if pi < pj:
                    i += 1
                    gi.append(i)
                    pi *= in_shape[i]
                else:
                    j += 1
                    gj.append(j)
                    pj *= resolved[j]
            groups.append((gi, gj))
            i += 1
            j += 1
        for gi, gj in groups:
            if s not in gi:
                continue
            if s != gi[0] and any(in_shape[d] != 1 for d in gi
                                  if d < s):
                self._fail(op, "reshape merges dims ahead of the "
                           "model-sharded dim")
            lead = gj[0]
            if resolved[lead] % self.size != 0:
                self._fail(op, "reshape target dim %d (size %d) does "
                           "not divide the model axis (%d shards)"
                           % (lead, resolved[lead], self.size))
            override = list(target)
            if override[lead] > 0:
                override[lead] //= self.size
                self.attr_override[id(op)] = {"shape": override}
            self.spec[op.output("Out")[0]] = lead
            return
        self._fail(op, "could not map the sharded dim through reshape")

    def _visit_transpose(self, op):
        xn = op.input("X")[0]
        s = self._sdim(xn)
        if s is None:
            return
        perm = list(op.attr("axis"))
        self.spec[op.output("Out")[0]] = perm.index(s)

    def _visit_spa(self, op):
        specs = {slot: self._sdim(op.input(slot)[0])
                 for slot in ("Q", "K", "V")}
        vals = set(specs.values())
        if vals == {None}:
            return
        last = len(self._shape(op.input("Q")[0])) - 1
        if vals != {last}:
            self._fail(op, "Q/K/V must all be sharded on the packed "
                       "head dim (got %r)" % specs)
        if op.input("Bias") and \
                self._sdim(op.input("Bias")[0]) is not None:
            self._fail(op, "attention bias cannot be model-sharded")
        n_heads = int(op.attr("n_heads"))
        if n_heads % self.size != 0:
            self._fail(op, "n_heads %d not divisible by the model axis "
                       "(%d shards)" % (n_heads, self.size))
        self.attr_override[id(op)] = {"n_heads": n_heads // self.size}
        self.spec[op.output("Out")[0]] = last

    # -- lowering shim -------------------------------------------------
    shape_only = False

    def lower(self, ctx, op):
        """Lower one op on per-shard values, applying this plan's
        region collectives and attr overrides around the registered
        rule. With ``shape_only`` set (the abstract boundary probe,
        which traces OUTSIDE the shard_map so no axis is bound) the
        collectives are skipped — they are shape-identities."""
        from ..parallel import tp as _tp
        from .registry import lower_op

        oid = id(op)
        saved = None
        if oid in self.copy_in and not self.shape_only:
            xn = op.input("X")[0]
            saved = (xn, ctx.env[xn])
            ctx.env[xn] = _tp.copy_to_tp_region(ctx.env[xn], self.axis)
        target = op
        if oid in self.attr_override:
            target = _AttrProxy(op, self.attr_override[oid])
        lower_op(ctx, target)
        if saved is not None:
            ctx.env[saved[0]] = saved[1]
        if oid in self.reduce_out and not self.shape_only:
            on = op.output("Out")[0]
            ctx.env[on] = _tp.reduce_from_tp_region(ctx.env[on],
                                                    self.axis)

    def local_shape(self, name):
        """Per-shard shape of ``name`` (global block shape with the
        sharded dim divided)."""
        shape = list(self._shape(name))
        s = self.spec.get(name)
        if s is not None and 0 <= s < len(shape) and shape[s] > 0:
            shape[s] = shape[s] // self.size
        return tuple(shape)


class BuildStrategy:
    """Reference ``details/build_strategy.h:58``. Knob fates on TPU:

    - ``enable_inplace`` — HONORED: toggles XLA buffer donation of the
      state pytree in every compiled step (off = keep old buffers live).
    - ``sync_batch_norm`` — inherent under GSPMD: a batch sharded over
      'dp' computes batch-norm statistics over the GLOBAL batch (XLA
      reduces across the sharded axis), which is exactly sync-BN; the
      flag is accepted for parity and not consulted.
    - ``fuse_all_reduce_ops`` / ``fuse_elewise_add_act_ops`` /
      ``fuse_all_optimizer_ops`` / ``memory_optimize`` — delegated to
      XLA's fusion/scheduling; accepted, not consulted.
    - ``reduce_strategy``/``gradient_scale_strategy`` — the GSPMD mean
      semantics make per-device grad scaling moot (loss is a global
      mean); accepted, not consulted.
    - ``num_trainers``/``trainer_id`` — multi-process identity comes from
      ``paddle_tpu.distributed`` env bootstrap instead.
    """

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.fuse_all_reduce_ops = True  # XLA fuses collectives by default
        self.fuse_elewise_add_act_ops = True
        self.fuse_all_optimizer_ops = True
        self.enable_inplace = True  # buffer donation
        self.memory_optimize = True
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    """Reference ``details/execution_strategy.h`` — thread counts are
    meaningless under XLA; kept for API parity."""

    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 100
        self.use_experimental_executor = True


class CompiledProgram:
    _uid_counter = itertools.count(1)

    def __init__(self, program_or_graph, build_strategy=None):
        self._uid = next(CompiledProgram._uid_counter)
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._places = None
        self._loss_name = None
        self._mesh = None
        self._sharded_feeds = None  # None => shard all feeds on dim 0
        self._seq_feeds = None      # name -> dim sharded over "sp"
        self._seq_fetches = None    # fetch name -> dim pinned to "sp"

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None, mesh_axes=("dp",), mesh_shape=None,
                           seq_feeds=None, seq_fetches=None):
        """GSPMD execution. ``mesh_axes``/``mesh_shape`` open the hybrid
        surface: e.g. mesh_axes=("dp","tp"), mesh_shape={"dp":2,"tp":4}
        lays parameters carrying a ``ParamAttr(shard=...)`` spec over the
        'tp' axis (Megatron-style) while the batch shards over 'dp'; XLA
        inserts the TP collectives over ICI.

        ``seq_feeds``: {feed name: dim} — that dim of the feed shards
        over the 'sp' (sequence) axis, composing with the dim-0 'dp'
        batch sharding; long-context programs feed token/cache arrays
        pre-split this way so no single device ever holds the full
        sequence. ``seq_fetches``: {fetch name: dim} — pins those fetch
        outputs to the same 'sp' layout instead of the replicated
        default, so a decode loop can feed a fetched KV cache straight
        back without an all-gather per token."""
        self._is_data_parallel = True
        self._mode = "gspmd"
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._places = places
        self._mesh_axes = _validate_mesh_axes(
            mesh_axes, honored={"host", "data", "model", "sp"},
            mode="with_data_parallel (GSPMD)")
        self._mesh_shape = dict(mesh_shape) if mesh_shape else None
        self._seq_feeds = dict(seq_feeds) if seq_feeds else None
        self._seq_fetches = dict(seq_fetches) if seq_fetches else None
        return self

    def with_pipeline(self, loss_name=None, places=None, num_microbatches=2,
                      microbatch_vars=None, mesh_axes=("stage",),
                      mesh_shape=None):
        """Pipeline-parallel execution of a Program whose optimizer was
        wrapped in ``PipelineOptimizer`` (cut points recorded on
        ``program._pipeline_cut_vars``).

        TPU-native redesign of the reference's section trainer
        (``PipelineTrainer`` trainer.h:114, scope queues + host threads):
        the forward ops are split into stages at the cut vars; all stages
        execute as ONE SPMD program over the ``stage`` mesh axis — each
        rank selects its stage with ``lax.switch``, activations hop
        rank→rank by ``ppermute``, and the GPipe fill/drain schedule is a
        ``lax.scan`` over ``M + S - 1`` ticks (see
        paddle_tpu/parallel/pipeline.py). The backward schedule falls out
        of differentiating the scan. Contract (GPipe's): the activation
        bundle at every cut shares one pytree of shapes.

        ``mesh_axes`` composes the schedule with the other parallelism
        axes — any of ``("host", "stage", "model", "data")`` with sizes
        in ``mesh_shape``:

        * ``host``/``data`` — hierarchical data parallelism: each
          microbatch's rows shard over these axes (DCN outer, ICI
          inner), grads pmean across them.
        * ``model`` — Megatron tensor parallelism inside every stage:
          params carrying ``ParamAttr(shard=...)`` specs naming 'model'
          are laid out column/row-parallel and the per-shard lowering
          inserts the two region collectives per block.

        Trace/build the model at the PER-SHARD microbatch size b and
        feed the full batch ``[M * data * host * b, ...]``: shape-
        carrying attrs (reshape targets) bake the trace batch, so the
        trace batch must equal what one shard sees per microbatch.
        """
        self._is_data_parallel = True
        self._mode = "pipeline"
        self._loss_name = loss_name
        self._places = places
        axes = _validate_mesh_axes(
            mesh_axes, honored={"host", "stage", "model", "data"},
            mode="with_pipeline", require=("stage",))
        unknown = [a for a in axes if a not in RESERVED_AXES]
        if unknown:
            raise ValueError(
                "with_pipeline mesh axes %r have no role in the "
                "schedule — use only %r" % (
                    unknown, sorted({"host", "stage", "model", "data"})))
        self._mesh_axes = axes
        self._mesh_shape = dict(mesh_shape) if mesh_shape else None
        self._num_microbatches = int(num_microbatches)
        self._microbatch_vars = (set(
            v.name if hasattr(v, "name") else str(v) for v in microbatch_vars)
            if microbatch_vars is not None else None)
        return self

    def with_explicit_collectives(self, loss_name=None, places=None,
                                  mesh_axes=("dp",), mesh_shape=None):
        """SPMD execution via shard_map: every op runs per-shard and the
        program's explicit collective ops (c_allreduce_* etc., inserted by
        the Fleet/collective transpiler) lower to real XLA collectives over
        the named mesh axes. This is the reference's Fleet-collective mode
        (transpiler/collective.py GradAllReduce) on ICI.

        ``mesh_axes``/``mesh_shape`` open the hierarchical surface:
        mesh_axes=("host","device"), mesh_shape={"host":2,"device":4}
        builds the 2-level mesh ``HierarchicalGradAllReduce`` targets —
        ring 0 resolves to 'host' (DCN), ring 1 to 'device' (ICI), and
        feeds/fetch reductions span BOTH axes (the batch shards over all
        8 shards, losses pmean over the full mesh)."""
        self._is_data_parallel = True
        self._mode = "shard_map"
        self._loss_name = loss_name
        self._places = places
        self._mesh_axes = _validate_mesh_axes(
            mesh_axes, honored={"host", "data"},
            mode="with_explicit_collectives (shard_map)")
        self._mesh_shape = dict(mesh_shape) if mesh_shape else None
        return self

    # ------------------------------------------------------------------
    @property
    def mesh(self):
        if not self._is_data_parallel:
            return None
        if self._mesh is None:
            import jax
            from jax.sharding import Mesh

            devices = self._places if self._places is not None else jax.devices()
            if isinstance(devices, int):
                devices = jax.devices()[:devices]
            axes = getattr(self, "_mesh_axes", ("dp",))
            # single-axis meshes go through the same sizing path so an
            # explicit mesh_shape is honored (and validated), not dropped
            arr = np.array(devices).reshape(
                self._mesh_axis_sizes(len(devices), axes))
            self._mesh = Mesh(arr, axes)
        return self._mesh

    def _mesh_axis_sizes(self, n, axes):
        shape = getattr(self, "_mesh_shape", None)
        if shape:
            missing = [a for a in axes if a not in shape]
            if missing:
                raise ValueError(
                    "mesh_shape %r is missing sizes for mesh axes %r"
                    % (shape, missing))
            sizes = tuple(int(shape[a]) for a in axes)
            if int(np.prod(sizes)) != n:
                raise ValueError(
                    "mesh_shape %r does not multiply to %d devices"
                    % (shape, n))
            return sizes
        # default: first axis takes all devices
        return (n,) + (1,) * (len(axes) - 1)

    def _on_trace_begin(self, ctx):
        if getattr(self, "_mode", "gspmd") == "shard_map":
            mesh = self.mesh
            ctx.shard_axes = list(mesh.axis_names)
            ctx.shard_sizes = dict(mesh.shape)

    def wrap_step(self, step, program, block, feed, fetch_names, state_names,
                  cache_key=None, cache_read_dirs=None):
        # cache_key/cache_read_dirs: the executor's persistent-compile-
        # cache key for this step (fluid/compile_cache.py); each wrapper
        # decorates its inner jit so a restart deserializes instead of
        # recompiling. None => wrap_jit is a no-op passthrough.
        self._cache_key = cache_key
        self._cache_read_dirs = cache_read_dirs
        mode = getattr(self, "_mode", "gspmd")
        if mode == "shard_map":
            return self._wrap_step_shard_map(step, feed, fetch_names,
                                             state_names)
        if mode == "pipeline":
            return self._wrap_step_pipeline(program, block, feed,
                                            fetch_names, state_names)
        return self._wrap_step_gspmd(step, block, feed, fetch_names,
                                     state_names)

    def _cache_wrap(self, jfn, label):
        return _compile_cache.wrap_jit(
            jfn, getattr(self, "_cache_key", None),
            read_dirs=getattr(self, "_cache_read_dirs", None), label=label)

    def _state_pspec(self, block, name):
        """PartitionSpec of a state var on the pipeline mesh — the
        ``shard_spec`` written by ``ParamAttr(shard=...)`` (optimizer
        slots inherit it), replicated otherwise. Strict: a spec naming
        an axis this mesh lacks is a config error at compile time."""
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        var = block._find_var_recursive(name) if block is not None \
            else None
        spec = getattr(var, "shard_spec", None) if var is not None \
            else None
        if spec is None:
            return P()
        missing = [a for a in spec if a is not None
                   and a not in mesh.shape]
        if missing:
            raise ValueError(
                "param %r shard spec %r names mesh axes %r absent from "
                "the mesh %r" % (name, spec, missing, dict(mesh.shape)))
        return P(*spec)

    def _pipeline_mb_names(self, feed):
        """Which feeds are batch-major (sliced into microbatches)?
        Explicit list wins; otherwise infer the batch size as the most
        common leading dim among feeds (a bare divisibility test would
        slice e.g. a (seq, seq) attention mask)."""
        M = self._num_microbatches
        explicit = getattr(self, "_microbatch_vars", None)
        if explicit is not None:
            mb_names = sorted(n for n in feed if n in explicit)
        else:
            from collections import Counter

            lead = Counter(np.shape(feed[n])[0] for n in feed
                           if np.ndim(feed[n]) >= 1)
            batch_dims = [d for d, c in lead.items()
                          if c == max(lead.values())] if lead else []
            if len(batch_dims) != 1:
                raise ValueError(
                    "cannot infer the batch-major feeds (leading dims %r); "
                    "pass microbatch_vars=[...] to with_pipeline" % (lead,))
            bdim = batch_dims[0]
            if bdim % M != 0:
                raise ValueError(
                    "batch dim %d not divisible by num_microbatches %d"
                    % (bdim, M))
            mb_names = sorted(n for n in feed
                              if np.ndim(feed[n]) >= 1
                              and np.shape(feed[n])[0] == bdim)
        full_names = sorted(n for n in feed if n not in mb_names)
        return mb_names, full_names

    def _build_pipeline_kernel(self, program, block, feed, fetch_names,
                               state_names):
        """The per-shard GPipe step body plus its layout metadata —
        shared by the single-step wrapper and the ``iters=k`` window
        wrapper (which scans this kernel).

        The kernel runs fully manual over EVERY mesh axis: 'stage'
        carries the switch/ppermute schedule, 'host'/'data' carry
        hierarchical DP (microbatch rows sharded, grads pmean'd), and
        'model' carries Megatron TP executed per-shard via the
        ``_ModelAxisPlan`` (partial-auto shard_map — GSPMD inside a
        manual region — aborts the SPMD partitioner on this jaxlib as
        soon as a ppermute appears, so nothing here is delegated to
        GSPMD)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from .registry import LowerCtx, lower_op

        mesh = self.mesh
        axis = "stage"
        n_stages = mesh.shape[axis]
        data_axes = tuple(a for a in mesh.axis_names
                          if a in ("host", "data") and mesh.shape[a] > 1)
        dp_total = int(np.prod([mesh.shape[a] for a in data_axes])) \
            if data_axes else 1
        M = self._num_microbatches

        segments, cut_groups, ad_idx = pipeline_segments(program, block)
        if len(cut_groups) != n_stages - 1:
            raise ValueError(
                "PipelineOptimizer recorded %d cut vars but the mesh has "
                "%d stage ranks (need exactly ranks-1 cuts)"
                % (len(cut_groups), n_stages))
        if ad_idx is None:
            raise ValueError(
                "pipeline mode needs a training program (no autodiff op "
                "found — call optimizer.minimize(loss) first)")
        ops = list(block.ops)
        ad_op = ops[ad_idx]
        post_ops = ops[ad_idx + 1:]
        fwd_ops = [o for seg in segments for o in seg]
        wrt = list(ad_op.attr("wrt"))
        grad_names = list(ad_op.attr("grad_names"))
        loss_name = self._loss_name or ad_op.attr("loss")

        plan = None
        if mesh.shape.get("model", 1) > 1:
            plan = _ModelAxisPlan(block, fwd_ops, "model",
                                  mesh.shape["model"])

        def low(ctx, o):
            if plan is not None:
                plan.lower(ctx, o)
            else:
                lower_op(ctx, o)

        mb_names, full_names = self._pipeline_mb_names(feed)
        for n in mb_names:
            b = np.shape(feed[n])[0]
            if b % (M * dp_total) != 0:
                raise ValueError(
                    "batch-major feed %r has %d rows, not divisible by "
                    "num_microbatches (%d) * data-parallel shards (%d); "
                    "feed [M * data * b, ...] rows where b is the "
                    "per-shard microbatch size the model was traced at"
                    % (n, b, M, dp_total))

        state_pspecs = {n: self._state_pspec(block, n)
                        for n in state_names}

        def local_state_shape(name, value):
            shape = list(np.shape(value))
            for d, a in enumerate(state_pspecs[name]):
                if a is not None and d < len(shape):
                    shape[d] //= mesh.shape[a]
            return tuple(shape)

        def _sds(value, shape=None):
            arr_shape = tuple(np.shape(value)) if shape is None else shape
            dtype = np.asarray(value).dtype if not hasattr(value, "dtype") \
                else value.dtype
            return jax.ShapeDtypeStruct(arr_shape, dtype)

        def _probe(env_vals):
            rng = _rng.root_key(0)
            prev, boundaries = None, []
            if plan is not None:
                plan.shape_only = True
            try:
                for r, seg in enumerate(segments):
                    env = dict(env_vals)
                    if r > 0:
                        for nm, v in zip(cut_groups[r - 1], prev):
                            env[nm] = v
                    ctx = LowerCtx(block, env, rng)
                    for o in seg:
                        low(ctx, o)
                    if r < n_stages - 1:
                        prev = tuple(env[nm] for nm in cut_groups[r])
                        boundaries.append(prev)
            finally:
                if plan is not None:
                    plan.shape_only = False
            return boundaries

        return {
            "mesh": mesh, "axis": axis, "n_stages": n_stages,
            "data_axes": data_axes, "dp_total": dp_total, "M": M,
            "segments": segments, "cut_groups": cut_groups,
            "post_ops": post_ops, "wrt": wrt, "grad_names": grad_names,
            "loss_name": loss_name, "plan": plan, "low": low,
            "mb_names": mb_names, "full_names": full_names,
            "state_pspecs": state_pspecs,
            "local_state_shape": local_state_shape,
            "probe": _probe, "sds": _sds,
        }

    def _finish_pipeline_kernel(self, ctxd, block, feed, state,
                                fetch_names, state_names):
        """Bind the boundary templates (needs actual state/feed values
        for local shapes) and return the per-shard kernel."""
        import jax
        import jax.numpy as jnp

        from .registry import LowerCtx, lower_op

        mesh = ctxd["mesh"]
        axis = ctxd["axis"]
        n_stages = ctxd["n_stages"]
        data_axes = ctxd["data_axes"]
        dp_total = ctxd["dp_total"]
        M = ctxd["M"]
        segments = ctxd["segments"]
        cut_groups = ctxd["cut_groups"]
        post_ops = ctxd["post_ops"]
        wrt, grad_names = ctxd["wrt"], ctxd["grad_names"]
        loss_name = ctxd["loss_name"]
        low = ctxd["low"]
        mb_names = ctxd["mb_names"]
        sds = ctxd["sds"]
        local_state_shape = ctxd["local_state_shape"]

        probe_in = {}
        for n in state_names:
            if n not in state:
                continue
            probe_in[n] = sds(state[n], local_state_shape(n, state[n]))
        for n, v in feed.items():
            shape = tuple(np.shape(v))
            if n in mb_names:
                shape = (shape[0] // (M * dp_total),) + shape[1:]
            probe_in[n] = sds(v, shape)
        boundaries = jax.eval_shape(ctxd["probe"], probe_in)
        if boundaries:
            tmpl0 = [(tuple(a.shape), a.dtype) for a in boundaries[0]]
            for r, b in enumerate(boundaries[1:], 1):
                t = [(tuple(a.shape), a.dtype) for a in b]
                if t != tmpl0:
                    raise ValueError(
                        "GPipe uniform-activation contract violated: "
                        "cut %r carries %r but cut %r carries %r — "
                        "every boundary must move one identical pytree "
                        "of activations (pad or re-cut)"
                        % (cut_groups[0], tmpl0, cut_groups[r], t))
            tmpl_sds = tmpl0
        else:
            tmpl_sds = []

        def make_stage(r, seg):
            in_group = cut_groups[r - 1] if r > 0 else None
            out_group = cut_groups[r] if r < n_stages - 1 else None
            is_last = r == n_stages - 1

            def stage(env_base, recv, rng):
                env = dict(env_base)
                if in_group is not None:
                    for nm, val in zip(in_group, recv):
                        env[nm] = val
                ctx = LowerCtx(block, env, rng)
                for o in seg:
                    low(ctx, o)
                zeros = tuple(jnp.zeros(s, d) for s, d in tmpl_sds)
                if is_last:
                    loss = env[loss_name]
                    if loss.ndim > 0:
                        loss = jnp.mean(loss)
                    return zeros, loss
                return (tuple(env[nm] for nm in out_group),
                        jnp.zeros((), "float32"))
            return stage

        stages = [make_stage(r, seg) for r, seg in enumerate(segments)]

        def kernel(params, rest_state, mb_feeds, full_feeds, rng):
            # advance the persistent RNG state every step (dropout masks
            # must differ across steps); stages draw from step_rng
            rng = _rng.wrap_key_data(rng)
            step_rng, next_rng = jax.random.split(rng)
            rng = step_rng
            rank = jax.lax.axis_index(axis)
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            tmpl = tuple(jnp.zeros(s, d) for s, d in tmpl_sds)

            def fwd(ps):
                def tick(carry, t):
                    recv, loss_acc = carry
                    mb = jnp.clip(t - rank, 0, M - 1)
                    env_base = {**rest_state, **ps,
                                **{k: jax.lax.dynamic_index_in_dim(
                                    v, mb, 0, keepdims=False)
                                   for k, v in mb_feeds.items()},
                                **full_feeds}
                    branches = [
                        (lambda eb, xr, rg, _s=s: _s(eb, xr, rg))
                        for s in stages
                    ]
                    y, l = jax.lax.switch(
                        rank, branches, env_base, recv,
                        jax.random.fold_in(rng, t))
                    valid = ((rank == n_stages - 1) & (t - rank >= 0)
                             & (t - rank < M))
                    loss_acc = loss_acc + jnp.where(valid, l, 0.0)
                    recv = tuple(jax.lax.ppermute(leaf, axis, perm)
                                 for leaf in y)
                    return (recv, loss_acc), None

                (_, loss_acc), _ = jax.lax.scan(
                    tick, (tmpl, jnp.zeros((), "float32")),
                    jnp.arange(M + n_stages - 1))
                # return the LOCAL contribution (nonzero on the last rank
                # only): grads flow back across ranks through the ppermute
                # transpose, and one psum below aggregates them — psumming
                # the loss in here too would double-count every cotangent
                return loss_acc / M

            local_loss, grads = jax.value_and_grad(fwd)(params)
            loss = jax.lax.psum(local_loss, axis)
            if data_axes:
                loss = jax.lax.pmean(loss, data_axes)

            def red(g):
                g = jax.lax.psum(g, axis)
                return jax.lax.pmean(g, data_axes) if data_axes else g

            grads = jax.tree_util.tree_map(red, grads)

            # run the post-autodiff ops (optimizer updates etc.) with the
            # pipelined grads bound to the autodiff op's output names;
            # model-sharded params update on their local shards
            env = {**rest_state, **params, **full_feeds,
                   **{k: v[0] for k, v in mb_feeds.items()}}
            env[loss_name] = loss
            for gn, wn in zip(grad_names, wrt):
                env[gn] = grads[wn]
            ctx = LowerCtx(block, env, rng)
            for o in post_ops:
                lower_op(ctx, o)

            new_params = {n: env[n] for n in params}
            new_rest = {n: env[n] for n in rest_state}
            fetches = []
            for fn_ in fetch_names:
                if fn_ == loss_name:
                    fetches.append(loss)
                elif fn_ in env:
                    fetches.append(env[fn_])
                else:
                    raise KeyError(
                        "pipeline mode can fetch the loss or persistable "
                        "vars, not intermediate %r" % fn_)
            return fetches, new_params, new_rest, _rng.key_data(next_rng)

        return kernel

    def _pipeline_specs(self, ctxd, fetch_names, state_names):
        """(in/out PartitionSpecs, fetch specs) for the pipeline
        shard_map: params/state by shard_spec, microbatch rows over the
        data axes, everything else replicated."""
        from jax.sharding import PartitionSpec as P

        data_axes = ctxd["data_axes"]
        state_pspecs = ctxd["state_pspecs"]
        wrt = set(ctxd["wrt"])
        mb_spec = P(None, data_axes) if data_axes else P()
        param_specs = {n: state_pspecs[n] for n in state_names
                       if n in wrt}
        rest_specs = {n: state_pspecs[n] for n in state_names
                      if n not in wrt}
        fetch_specs = [state_pspecs.get(n, P()) for n in fetch_names]
        return mb_spec, param_specs, rest_specs, fetch_specs

    def _wrap_step_pipeline(self, program, block, feed, fetch_names,
                            state_names):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        ctxd = self._build_pipeline_kernel(program, block, feed,
                                           fetch_names, state_names)
        mesh = ctxd["mesh"]
        M, n_stages = ctxd["M"], ctxd["n_stages"]
        dp_total = ctxd["dp_total"]
        mb_names = ctxd["mb_names"]
        plan = ctxd["plan"]
        repl = NamedSharding(mesh, P())
        mb_spec, param_specs, rest_specs, fetch_specs = \
            self._pipeline_specs(ctxd, fetch_names, state_names)
        _M_PIPE_BUBBLE.set((n_stages - 1) / (M + n_stages - 1))
        tp_bytes_per_step = (plan.psum_bytes * M) if plan else 0

        jfn_box = {}

        def fn(state, feed_vals, rng):
            params = {n: state[n] for n in state if n in param_specs}
            rest = {n: state[n] for n in state if n not in param_specs}
            mbf, fullf = {}, {}
            for k, v in feed_vals.items():
                if k in mb_names:
                    arr = jnp.asarray(v)
                    mbf[k] = arr.reshape((M, arr.shape[0] // M)
                                         + arr.shape[1:])
                else:
                    fullf[k] = jnp.asarray(v)
            if "jfn" not in jfn_box:
                kernel = self._finish_pipeline_kernel(
                    ctxd, block, feed_vals, state, fetch_names,
                    state_names)
                # spec dicts keyed by the RUNTIME state split (state may
                # carry vars the trace-time state_names missed)
                jfn_box["p_specs"] = {n: param_specs.get(n, P())
                                      for n in params}
                jfn_box["r_specs"] = {n: rest_specs.get(n, P())
                                      for n in rest}
                smapped = jax.shard_map(
                    kernel, mesh=mesh,
                    in_specs=(jfn_box["p_specs"], jfn_box["r_specs"],
                              {n: mb_spec for n in mbf},
                              {n: P() for n in fullf}, P()),
                    out_specs=(fetch_specs, jfn_box["p_specs"],
                               jfn_box["r_specs"], P()),
                    check_vma=False)
                donate = ((0, 1) if self._build_strategy.enable_inplace
                          else ())
                jfn_box["jfn"] = self._cache_wrap(
                    jax.jit(smapped, donate_argnums=donate), "pipeline")
            put_state = lambda tree, specs: {
                k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                for k, v in tree.items()}
            fetches, new_params, new_rest, new_rng = jfn_box["jfn"](
                put_state(params, jfn_box["p_specs"]),
                put_state(rest, jfn_box["r_specs"]),
                {k: jax.device_put(v, NamedSharding(mesh, mb_spec))
                 for k, v in mbf.items()},
                {k: jax.device_put(v, repl) for k, v in fullf.items()},
                jax.device_put(rng, repl))
            _M_PIPE_MB.inc(M)
            if tp_bytes_per_step:
                _M_TP_BYTES.inc(tp_bytes_per_step)
            new_state = dict(new_rest)
            new_state.update(new_params)
            return fetches, new_state, new_rng

        return fn

    def _wrap_step_shard_map(self, step, feed, fetch_names, state_names):
        """SPMD per-shard execution; program collectives do the syncing."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        # fetch reductions span the WHOLE mesh: on a hierarchical
        # ("host","device") mesh the loss must average over all H*D
        # shards, not just the first axis
        axis = tuple(mesh.axis_names)
        repl = NamedSharding(mesh, P())

        feed_specs = {n: self.feed_sharding(feed[n]).spec for n in feed}

        def inner(state, feed_vals, rng):
            fetches, new_state, new_rng = step(state, feed_vals, rng)
            # fetches are per-shard; average them for the host (the
            # reference returns the averaged loss across trainers)
            out = []
            for f in fetches:
                if jnp.issubdtype(f.dtype, jnp.floating):
                    out.append(jax.lax.pmean(f, axis))
                else:
                    out.append(jax.lax.pmax(f, axis))
            return out, new_state, new_rng

        smapped = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=({n: P() for n in state_names}, feed_specs, P()),
            out_specs=([P() for _ in fetch_names], {n: P() for n in state_names}, P()),
            check_vma=False,
        )
        donate = (0,) if self._build_strategy.enable_inplace else ()
        jfn = self._cache_wrap(jax.jit(smapped, donate_argnums=donate),
                               "shard_map")
        feed_shardings = {n: NamedSharding(mesh, feed_specs[n]) for n in feed}

        def fn(state, feed_vals, rng):
            state = {k: jax.device_put(v, repl) for k, v in state.items()}
            feed_vals = {k: jax.device_put(v, feed_shardings[k])
                         for k, v in feed_vals.items()}
            rng = jax.device_put(rng, repl)
            return jfn(state, feed_vals, rng)

        return fn

    def feed_sharding(self, value, batch_dim=0, name=None):
        """The ``NamedSharding`` this strategy lays a feed array out
        with — the single source of truth the step wrappers AND the
        ahead-of-time stagers (``fluid.reader.DeviceStager``,
        ``Executor.train_from_dataset``, the ``iters=k`` window
        prefetch) share, so prefetched batches land pre-sharded across
        the mesh instead of funneling through device 0.

        ``batch_dim`` is the axis carrying the batch (1 for an
        ``iters=k`` stacked ``[k, batch, ...]`` feed whose leading axis
        is the iteration index). Returns the batch-sharded layout when
        the strategy shards feeds ('dp' under GSPMD, the first mesh
        axis under shard_map) and the batch dim divides evenly,
        replicated otherwise; ``None`` when the strategy stages feeds
        itself (pipeline mode) or no mesh is attached.

        ``name`` keys the GSPMD ``seq_feeds`` table: a registered feed
        additionally shards that dim over 'sp' (composing with the
        batch-over-'dp' split) when the dim divides the axis size."""
        if not self._is_data_parallel:
            return None
        mode = getattr(self, "_mode", "gspmd")
        if mode == "pipeline":
            return None
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        ndim = np.ndim(value)
        seq_feeds = getattr(self, "_seq_feeds", None)
        if (mode == "gspmd" and seq_feeds and name in seq_feeds
                and "sp" in mesh.shape):
            sdim = int(seq_feeds[name])
            if sdim != batch_dim and ndim > sdim and \
                    np.shape(value)[sdim] % mesh.shape["sp"] == 0:
                spec = [None] * ndim
                spec[sdim] = "sp"
                if "dp" in mesh.shape and ndim > batch_dim and \
                        np.shape(value)[batch_dim] % mesh.shape["dp"] == 0:
                    spec[batch_dim] = "dp"
                return NamedSharding(mesh, P(*spec))
        if mode == "shard_map" and len(mesh.axis_names) > 1:
            # hierarchical mesh: the batch shards over EVERY axis (each
            # of the H*D shards is one data-parallel rank); fall back to
            # the leading axis when only its size divides the batch
            axes = tuple(mesh.axis_names)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            if ndim > batch_dim and \
                    np.shape(value)[batch_dim] % total == 0:
                spec = [None] * ndim
                spec[batch_dim] = axes
                return NamedSharding(mesh, P(*spec))
        axis = "dp" if mode == "gspmd" else mesh.axis_names[0]
        if axis in mesh.shape and ndim > batch_dim and \
                np.shape(value)[batch_dim] % mesh.shape[axis] == 0:
            spec = [None] * ndim
            spec[batch_dim] = axis
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    def _state_sharding(self, block, name, mesh, repl, shape=None):
        """Param layout: ``ParamAttr(shard=...)`` specs over the mesh,
        everything else replicated (shared by the single-step and
        step-batched GSPMD wrappers). With ``shape`` given (the restore
        path, where the mesh may have shrunk since the spec was
        written), a spec that no longer fits degrades to replicated —
        counted in ``state_reshard_replicated_total`` — instead of
        raising; compile-time callers pass no shape and keep the strict
        error."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        var = block._find_var_recursive(name) if block is not None \
            else None
        spec = getattr(var, "shard_spec", None) if var is not None \
            else None
        if spec is None:
            return repl
        missing = [a for a in spec if a is not None
                   and a not in mesh.shape]
        if missing:
            if shape is None:
                raise ValueError(
                    "param %r shard spec %r names mesh axes %r absent "
                    "from the mesh %r" % (name, spec, missing,
                                          dict(mesh.shape)))
            _M_RESHARD_REPL.inc()
            import logging

            logging.getLogger(__name__).warning(
                "param %r shard spec %r names mesh axes %r absent from "
                "the current mesh %r; restoring replicated",
                name, spec, missing, dict(mesh.shape))
            return repl
        if shape is not None:
            for d, a in enumerate(spec):
                if a is None:
                    continue
                if d >= len(shape) or shape[d] % mesh.shape[a] != 0:
                    _M_RESHARD_REPL.inc()
                    import logging

                    logging.getLogger(__name__).warning(
                        "param %r shape %r does not divide over mesh "
                        "axis %r (size %d); restoring replicated",
                        name, tuple(shape), a, mesh.shape[a])
                    return repl
        return NamedSharding(mesh, P(*spec))

    def state_sharding(self, block, name, value=None):
        """The ``NamedSharding`` a persistable var takes under this
        strategy — the single source of truth
        ``fluid.io.CheckpointManager.restore`` uses to reshard a
        restored checkpoint onto the CURRENT mesh, which after an
        elastic reformation (``distributed.launch`` shrink-to-
        survivors) may be smaller than the mesh that saved it. With
        ``value`` given, a spec that no longer fits the mesh (axis
        gone, dim not divisible) degrades to replicated instead of
        raising. Returns None when the strategy has no mesh (plain
        program — nothing to reshard onto). Pipeline mode answers too:
        a checkpoint saved 'model'-sharded on a 1x4 GSPMD mesh restores
        onto a 2x2 stage-by-model pipeline mesh through the same
        degradation path (specs whose axes survived reshard, the rest
        replicate and count in ``state_reshard_replicated_total``)."""
        if not self._is_data_parallel:
            return None
        mesh = self.mesh
        if mesh is None:
            return None
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        repl = NamedSharding(mesh, P())
        return self._state_sharding(
            block, name, mesh, repl,
            shape=np.shape(value) if value is not None else None)

    def _fetch_sharding(self, name, mesh, repl):
        """Fetch layout: replicated unless registered in ``seq_fetches``
        — those pin the given dim to 'sp' so a decode loop can feed the
        fetched (still-sharded) KV cache straight back without the
        per-token all-gather a replicated fetch would force."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        seq_fetches = getattr(self, "_seq_fetches", None)
        if not seq_fetches or name not in seq_fetches or \
                "sp" not in mesh.shape:
            return repl
        sdim = int(seq_fetches[name])
        spec = [None] * (sdim + 1)
        spec[sdim] = "sp"
        return NamedSharding(mesh, P(*spec))

    def _wrap_step_gspmd(self, step, block, feed, fetch_names, state_names):
        """jit the lowered step under the mesh: batch over 'dp', params
        laid out by their ``shard_spec`` (TP), everything else replicated.
        XLA/GSPMD inserts all collectives (grad allreduce over dp, TP
        gather/reduce-scatter) from these layouts."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        repl = NamedSharding(mesh, P())

        feed_shardings = {n: self.feed_sharding(feed[n], name=n)
                          for n in feed}
        state_shardings = {n: self._state_sharding(block, n, mesh, repl)
                           for n in state_names}
        in_shardings = (
            state_shardings,
            feed_shardings,
            repl,
        )
        # Pin the new-state layouts to the input layouts: a donated state
        # buffer must alias an identically-sharded output, and leaving the
        # state output unconstrained lets XLA pick per-shard layouts that
        # break the aliasing on older jax builds.
        out_shardings = ([self._fetch_sharding(n, mesh, repl)
                          for n in fetch_names], state_shardings, repl)
        donate = (0,) if self._build_strategy.enable_inplace else ()
        jfn = self._cache_wrap(jax.jit(
            step,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=donate,
        ), "gspmd")

        def fn(state, feed_vals, rng):
            # Committed single-device arrays (e.g. from the startup program)
            # must be explicitly resharded onto the mesh before the jit call.
            state = {k: jax.device_put(v, state_shardings.get(k, repl))
                     for k, v in state.items()}
            feed_vals = {
                k: jax.device_put(v, feed_shardings[k]) for k, v in feed_vals.items()
            }
            rng = jax.device_put(rng, repl)
            return jfn(state, feed_vals, rng)

        return fn

    def wrap_batched_step(self, batched, block, stacked_feed,
                          invariant_feed, fetch_names, state_names,
                          cache_key=None, cache_read_dirs=None,
                          program=None, iters=None):
        """Step-batched (``iters=k``) execution under this strategy.

        GSPMD: stacked feeds shard their SECOND axis over 'dp' (the
        leading axis is the iteration index the device-side scan
        slices), invariant feeds shard their leading axis like
        single-step feeds, params follow their ``shard_spec``.

        Pipeline: the window scans the GPipe step kernel INSIDE the
        shard_map (``program``/``iters`` required), so k steps of the
        fill/drain schedule run back-to-back on device — results are
        bit-identical to k single ``run()`` calls because the scan body
        IS the single-step kernel.

        shard_map (explicit collectives) schedules its own device-side
        loop and is refused with a typed error."""
        mode = getattr(self, "_mode", "gspmd")
        if mode == "pipeline":
            if program is None:
                raise ValueError(
                    "pipeline iters>1 needs the Program (cut vars live "
                    "on it); callers must pass program=")
            self._cache_key = cache_key
            self._cache_read_dirs = cache_read_dirs
            return self._wrap_batched_pipeline(
                program, block, stacked_feed, invariant_feed,
                fetch_names, state_names, iters)
        if mode != "gspmd":
            raise UnsupportedStrategyError(
                "iters>1 does not support the %r strategy; supported "
                "strategies: 'gspmd' (with_data_parallel) and "
                "'pipeline' (with_pipeline). %r schedules its own "
                "device-side loop — drive steps from the host instead"
                % (mode, mode))
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        repl = NamedSharding(mesh, P())

        state_shardings = {n: self._state_sharding(block, n, mesh, repl)
                           for n in state_names}
        stacked_shardings = {n: self.feed_sharding(stacked_feed[n],
                                                   batch_dim=1)
                             for n in stacked_feed}
        invariant_shardings = {n: self.feed_sharding(invariant_feed[n])
                               for n in invariant_feed}
        self._cache_key = cache_key
        self._cache_read_dirs = cache_read_dirs
        donate = (0,) if self._build_strategy.enable_inplace else ()
        jfn = self._cache_wrap(jax.jit(
            batched,
            in_shardings=(state_shardings, stacked_shardings,
                          invariant_shardings, repl),
            out_shardings=([repl for _ in fetch_names], None, repl),
            donate_argnums=donate,
        ), "gspmd_batched")

        def fn(state, stacked_vals, invariant_vals, rng):
            state = {k: jax.device_put(v, state_shardings.get(k, repl))
                     for k, v in state.items()}
            stacked_vals = {k: jax.device_put(v, stacked_shardings[k])
                            for k, v in stacked_vals.items()}
            invariant_vals = {k: jax.device_put(v, invariant_shardings[k])
                              for k, v in invariant_vals.items()}
            rng = jax.device_put(rng, repl)
            return jfn(state, stacked_vals, invariant_vals, rng)

        return fn

    def _wrap_batched_pipeline(self, program, block, stacked_feed,
                               invariant_feed, fetch_names, state_names,
                               iters):
        """``iters=k`` window over the GPipe kernel: a ``lax.scan`` over
        the k iterations runs INSIDE the shard_map, its body being
        exactly the single-step kernel — so the window's per-step
        results are bit-identical to k single steps (same op order,
        same RNG chain), just without k host round-trips."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # per-iteration feed template drives the microbatch/full split
        # and the abstract shape probe
        feed_tmpl = {n: v[0] for n, v in stacked_feed.items()}
        feed_tmpl.update(invariant_feed)
        ctxd = self._build_pipeline_kernel(program, block, feed_tmpl,
                                           fetch_names, state_names)
        mesh = ctxd["mesh"]
        M, n_stages = ctxd["M"], ctxd["n_stages"]
        mb_names = ctxd["mb_names"]
        plan = ctxd["plan"]
        data_axes = ctxd["data_axes"]
        if iters is not None:
            k = int(iters)
        elif stacked_feed:
            k = int(np.shape(next(iter(stacked_feed.values())))[0])
        else:
            raise ValueError(
                "pipeline iters>1 with no stacked feeds needs iters=")
        repl = NamedSharding(mesh, P())
        mb_spec, param_specs, rest_specs, fetch_specs = \
            self._pipeline_specs(ctxd, fetch_names, state_names)
        # traj entries carry a leading k axis the per-step spec must skip
        traj_specs = [P(*((None,) + tuple(s))) for s in fetch_specs]
        stk_mb_spec = P(None, None, data_axes) if data_axes else P()
        _M_PIPE_BUBBLE.set((n_stages - 1) / (M + n_stages - 1))
        tp_bytes = (plan.psum_bytes * M * k) if plan else 0
        jfn_box = {}

        def fn(state, stacked_vals, invariant_vals, rng):
            params = {n: state[n] for n in state if n in param_specs}
            rest = {n: state[n] for n in state if n not in param_specs}
            stk_mb, stk_full, inv_mb, inv_full = {}, {}, {}, {}
            for n, v in stacked_vals.items():
                arr = jnp.asarray(v)
                if n in mb_names:
                    stk_mb[n] = arr.reshape(
                        (arr.shape[0], M, arr.shape[1] // M)
                        + arr.shape[2:])
                else:
                    stk_full[n] = arr
            for n, v in invariant_vals.items():
                arr = jnp.asarray(v)
                if n in mb_names:
                    inv_mb[n] = arr.reshape((M, arr.shape[0] // M)
                                            + arr.shape[1:])
                else:
                    inv_full[n] = arr
            if "jfn" not in jfn_box:
                feed0 = {n: v[0] for n, v in stacked_vals.items()}
                feed0.update(invariant_vals)
                kernel = self._finish_pipeline_kernel(
                    ctxd, block, feed0, state, fetch_names, state_names)
                jfn_box["p_specs"] = {n: param_specs.get(n, P())
                                      for n in params}
                jfn_box["r_specs"] = {n: rest_specs.get(n, P())
                                      for n in rest}

                def window(params, rest_state, stk_mb, stk_full,
                           inv_mb, inv_full, rng):
                    def body(carry, xs):
                        p, r, rk = carry
                        mb_i, full_i = xs
                        fetches, p, r, rk = kernel(
                            p, r, {**inv_mb, **mb_i},
                            {**inv_full, **full_i}, rk)
                        return (p, r, rk), fetches

                    (p, r, rk), traj = jax.lax.scan(
                        body, (params, rest_state, rng),
                        (stk_mb, stk_full), length=k)
                    return traj, p, r, rk

                smapped = jax.shard_map(
                    window, mesh=mesh,
                    in_specs=(jfn_box["p_specs"], jfn_box["r_specs"],
                              {n: stk_mb_spec for n in stk_mb},
                              {n: P() for n in stk_full},
                              {n: mb_spec for n in inv_mb},
                              {n: P() for n in inv_full}, P()),
                    out_specs=(traj_specs, jfn_box["p_specs"],
                               jfn_box["r_specs"], P()),
                    check_vma=False)
                donate = ((0, 1) if self._build_strategy.enable_inplace
                          else ())
                jfn_box["jfn"] = self._cache_wrap(
                    jax.jit(smapped, donate_argnums=donate),
                    "pipeline_batched")
            put = lambda tree, spec_of: {
                kk: jax.device_put(vv, NamedSharding(mesh, spec_of(kk)))
                for kk, vv in tree.items()}
            traj, new_params, new_rest, new_rng = jfn_box["jfn"](
                put(params, jfn_box["p_specs"].__getitem__),
                put(rest, jfn_box["r_specs"].__getitem__),
                put(stk_mb, lambda _n: stk_mb_spec),
                put(stk_full, lambda _n: P()),
                put(inv_mb, lambda _n: mb_spec),
                put(inv_full, lambda _n: P()),
                jax.device_put(rng, repl))
            _M_PIPE_MB.inc(M * k)
            if tp_bytes:
                _M_TP_BYTES.inc(tp_bytes)
            new_state = dict(new_rest)
            new_state.update(new_params)
            return traj, new_state, new_rng

        return fn
