"""The ``autodiff`` op: gradient computation as a functional transform.

TPU-first replacement for the reference's per-op grad machinery
(``GradOpDescMakerBase`` grad_op_desc_maker.h + ``backward.py:933``'s
op-by-op grad program synthesis): instead of synthesizing hundreds of
``*_grad`` ops, ``append_backward`` inserts ONE ``autodiff`` op whose
lowering replays the forward ops as a pure function and differentiates it
with ``jax.grad``.

The compiled step holds ONE forward. ``lower_block`` has lowered the same
ops once already (the primal), so the replay returns its forward values as
the grad transform's auxiliary output and ``_autodiff`` rebinds those names
in ``ctx.env``: every later reader (fetches, metric ops, optimizer ops, the
state the executor commits) takes the replay's arrays, the primal chain is
dead from the first forward op's output on, and ``jit`` removes it. Nothing
is left for XLA's CSE to merge - it cannot merge two ``tpu_custom_call``s,
nor anything downstream of them. In a trace the forward therefore carries
the replay's scope, ``autodiff/jvp(<op>)``. What keeps its primal binding:
the ``wrt`` vars, non-array entries (LoD lengths, tensor arrays) and what a
``jax.checkpoint`` segment does not hand on. ``ctx.written`` stays as the
primal lowering recorded it.

Random ops replay with recorded PRNG keys (``LowerCtx.replay_keys``) and
``stop_gradient`` is the identity going forward, so the differentiated
forward is bit-identical to the primal (the reference saves dropout masks
for backward - same guarantee, no memory cost) and the rebinding changes no
number.

``stop_gradient`` var markers are honored by wrapping those vars in
``lax.stop_gradient`` during the replay.
"""

from ..registry import LowerCtx, register, registry


def _run_ops(rctx, ops, wrt_names):
    """Lower `ops` in order on rctx, honoring stop_gradient markers."""
    import jax

    from ..registry import lower_op

    for o in ops:
        lower_op(rctx, o)
        for name in o.output_arg_names():
            v = rctx.var(name)
            if v is not None and v.stop_gradient and name not in wrt_names:
                rctx.env[name] = jax.lax.stop_gradient(rctx.env[name])


def _replay_forward_checkpointed(ctx, prior_ops, wrt_names, overrides,
                                 checkpoints):
    """Replay the forward split into segments at the checkpoint vars, each
    wrapped in ``jax.checkpoint`` so XLA saves only segment boundaries and
    rematerializes intermediate activations during the backward pass
    (reference recompute: ``backward.py:576``
    ``_append_backward_ops_with_checkpoints_``).

    Only the loss needs to survive to the caller: each segment returns just
    the env entries later segments (or the loss) consume, so the residual
    set the grad transform saves is those boundary values - and what a
    producer inside the segment has marked with
    ``kernels.common.keep_across_recompute`` (a kernel's output that is
    dear to make for what it holds: attention's ``o`` and row logsumexp,
    ``sparse_index``'s mask, the delta rule's ``o``, states and
    inverses). Everything else in a segment is made again
    in the backward pass; a segment with no marked value lowers as under a
    bare ``jax.checkpoint``.
    """
    import jax

    from ...kernels.common import RECOMPUTE_KEEP, recompute_segment

    keep_marked = jax.checkpoint_policies.save_only_these_names(
        RECOMPUTE_KEEP)

    # segment boundaries: after the op that (last) produces each checkpoint
    producer = {}
    for i, o in enumerate(prior_ops):
        for name in o.output_arg_names():
            producer[name] = i
    cut_idx = sorted({producer[c] for c in checkpoints if c in producer})
    segments = []
    start = 0
    for ci in cut_idx:
        segments.append(prior_ops[start:ci + 1])
        start = ci + 1
    if start < len(prior_ops):
        segments.append(prior_ops[start:])
    if len(segments) <= 1:
        renv = _replay_forward(ctx, prior_ops, wrt_names, overrides)
        return renv

    # vars each later segment reads (so each segment's output pytree is the
    # minimal carry); key slices per segment from the primal lowering record
    spans = ctx.op_key_spans
    all_keys = list(ctx.used_keys)
    seg_keys, seg_needs = [], []
    for seg in segments:
        ks = [spans.get(id(o), (0, 0)) for o in seg]
        lo = min((s for s, _ in ks), default=0)
        hi = max((e for _, e in ks), default=0)
        seg_keys.append(all_keys[lo:hi])
        seg_needs.append(set())
    for i in range(len(segments)):
        for later in segments[i + 1:]:
            for o in later:
                seg_needs[i].update(o.input_arg_names())

    env = dict(ctx.initial_env)
    env.update(overrides)
    for i, seg in enumerate(segments):
        keep = seg_needs[i]
        is_last = i == len(segments) - 1

        def run_seg(env_in, _seg=seg, _keys=seg_keys[i], _keep=keep,
                    _last=is_last):
            rctx = LowerCtx(ctx.block, dict(env_in), ctx.initial_rng,
                            mesh=ctx.mesh, replay_keys=list(_keys))
            rctx.initial_env = ctx.initial_env
            rctx.initial_rng = ctx.initial_rng
            _run_ops(rctx, _seg, wrt_names)
            if _last:
                return rctx.env
            out = dict(env_in)
            for k in _keep:
                if k in rctx.env:
                    out[k] = rctx.env[k]
            return out

        if is_last:
            env = run_seg(env)
        else:
            with recompute_segment():
                env = jax.checkpoint(run_seg, policy=keep_marked)(env)
    return env


def _replay_forward(ctx, prior_ops, wrt_names, overrides, sparse_eps=None):
    """Build env after replaying prior_ops with wrt vars overridden.
    ``sparse_eps``: {param_name: zeros-like-lookup-out} injected additively
    into that param's lookup output during replay, so the cotangent w.r.t.
    eps IS the SelectedRows values gradient (no dense W-grad ever built)."""
    renv = dict(ctx.initial_env)
    renv.update(overrides)
    rctx = LowerCtx(
        ctx.block,
        renv,
        ctx.initial_rng,
        mesh=ctx.mesh,
        replay_keys=list(ctx.used_keys),
    )
    rctx.initial_env = ctx.initial_env
    rctx.initial_rng = ctx.initial_rng
    if sparse_eps:
        rctx.sparse_eps = sparse_eps
    _run_ops(rctx, prior_ops, wrt_names)
    return renv


@register("autodiff")
def _autodiff(ctx, op):
    import jax

    loss_name = op.attr("loss")
    wrt_names = list(op.attr("wrt"))
    grad_names = list(op.attr("grad_names"))
    loss_scale = op.attr("loss_scale", 1.0)
    # AMP dynamic loss scaling: the scale is a runtime *variable* (reference
    # decorator.py:135 multiplies the loss by the loss_scaling var), so the
    # dynamically updated value takes effect on the next step — a static
    # attr would freeze the scale at its initial value.
    scale_var = op.attr("loss_scale_var", None)
    if scale_var is not None:
        import jax.numpy as jnp

        # composes with the static attr (e.g. GradAllReduce's 1/nranks)
        loss_scale = loss_scale * jnp.reshape(
            jax.lax.stop_gradient(ctx.get(scale_var)), ()).astype("float32")

    block = ctx.block
    idx = next(i for i, o in enumerate(block.ops) if o is op)
    prior_ops = block.ops[:idx]

    wrt_vals = []
    for n in wrt_names:
        v = ctx.initial_env.get(n)
        if v is None:
            v = ctx.get(n)
        wrt_vals.append(v)

    checkpoints = op.attr("checkpoints", None)
    sparse_wrt = op.attr("sparse_wrt", None) or []
    # host-table (parameter-server) lookups: no device param, the cotangent
    # at the lookup output is PUSHED to the host store (ops/distributed_ops)
    dist_push = op.attr("dist_push", None) or []
    sparse_names = {s[0] for s in sparse_wrt}
    dense_idx = [i for i, n in enumerate(wrt_names) if n not in sparse_names]
    dense_names = [wrt_names[i] for i in dense_idx]
    rebound = {n for o in prior_ops for n in o.output_arg_names()}
    rebound.difference_update(wrt_names)

    def run_fwd(overrides, sparse_eps):
        if checkpoints:
            if sparse_eps:
                raise NotImplementedError(
                    "recompute + sparse embedding grads not supported yet")
            renv = _replay_forward_checkpointed(
                ctx, prior_ops, set(wrt_names), overrides, list(checkpoints))
        else:
            renv = _replay_forward(ctx, prior_ops, set(wrt_names), overrides,
                                   sparse_eps)
        loss = renv[loss_name]
        if loss.ndim > 0:
            import jax.numpy as jnp

            loss = jnp.sum(loss)
        forward_values = {n: renv[n] for n in rebound
                          if isinstance(renv.get(n), jax.Array)}
        return loss * loss_scale, forward_values

    if sparse_wrt or dist_push:
        import numpy as np
        import jax.numpy as jnp

        # eps keyed by lookup OUTPUT name (unique per lookup op; works for
        # host-table lookups which have no W input)
        eps_outs = [s[2] for s in sparse_wrt] + [d[2] for d in dist_push]
        eps0 = [jnp.zeros_like(ctx.get(o)) for o in eps_outs]
        dense_vals = [wrt_vals[i] for i in dense_idx]

        def fwd2(dvals, evals):
            eps_map = dict(zip(eps_outs, evals))
            return run_fwd(dict(zip(dense_names, dvals)), eps_map)

        (gdense, geps), forward_values = jax.grad(
            fwd2, argnums=(0, 1), has_aux=True)(dense_vals, eps0)
        for i, g in zip(dense_idx, gdense):
            ctx.set(grad_names[i], g)
        n_sparse = len(sparse_wrt)
        for (pname, ids_name, _), ge in zip(sparse_wrt, geps[:n_sparse]):
            ids = ctx.get(ids_name)
            rows = jnp.reshape(ids, (-1,)).astype("int32")
            values = jnp.reshape(ge, (rows.shape[0], -1))
            gname = grad_names[wrt_names.index(pname)]
            ctx.set(gname, values)
            ctx.set(gname + "@ROWS", rows)
        for (tname, ids_name, out_name, lr, optname), ge in zip(
                dist_push, geps[n_sparse:]):
            # bind the cotangent; the actual host push is a separate
            # `distributed_push` op appended after the autodiff op, so AMP
            # can unscale/overflow-gate the payload before it leaves the
            # device (ops/distributed_ops.py)
            ids = ctx.get(ids_name)
            # int32 on device (x64 is disabled; widening happens at the host
            # boundary in table.push — host tables beyond 2^31 rows would
            # need int64 device ids, which the chip doesn't carry anyway)
            rows = jnp.reshape(ids, (-1,)).astype(np.dtype("int32"))
            values = jnp.reshape(
                ge.astype(np.dtype("float32")), (rows.shape[0], -1))
            ctx.set(out_name + "@PS_GRAD", values)
            ctx.set(out_name + "@PS_ROWS", rows)
    else:
        grads, forward_values = jax.grad(
            lambda vals: run_fwd(dict(zip(wrt_names, vals)), None),
            has_aux=True)(wrt_vals)
        for gname, g in zip(grad_names, grads):
            ctx.set(gname, g)
    # one forward in the step: later readers take the replay's values (not
    # ctx.set - ctx.written stays what the primal lowering recorded)
    ctx.env.update(forward_values)


@register("calc_gradient")
def _calc_gradient(ctx, op):
    """Grad of arbitrary targets w.r.t. arbitrary inputs with optional
    user-supplied target gradients (reference ``backward.py:1199``)."""
    import jax

    target_names = list(op.attr("targets"))
    wrt_names = list(op.attr("wrt"))
    grad_names = list(op.attr("grad_names"))
    tg_names = op.attr("target_gradients") or []

    block = ctx.block
    idx = next(i for i, o in enumerate(block.ops) if o is op)
    prior_ops = block.ops[:idx]

    wrt_vals = []
    for n in wrt_names:
        v = ctx.initial_env.get(n)
        if v is None:
            v = ctx.get(n)
        wrt_vals.append(v)

    def fwd(vals):
        renv = _replay_forward(ctx, prior_ops, set(wrt_names), dict(zip(wrt_names, vals)))
        return [renv[t] for t in target_names]

    _, vjp_fn = jax.vjp(fwd, wrt_vals)
    if tg_names:
        cotangents = [ctx.get(n) for n in tg_names]
    else:
        import jax.numpy as jnp

        cotangents = [jnp.ones_like(ctx.get(t)) for t in target_names]
    (grads,) = vjp_fn(cotangents)
    for gname, g in zip(grad_names, grads):
        ctx.set(gname, g)
