"""Sparse-expert ops a Program can reach: the router over ALL experts and
the dropless expert layer over the experts HELD here.

One expert-parallel rank's share (the ``model-configs`` guide's cut): the
router keeps its published width and its experts per token; ``moe_experts``
is told which experts it holds (``expert_offset`` and the leading axis of
its weights), computes their part of the result for the (token, choice)
pairs routed to them, and leaves out what absent experts would add. On one
chip the layer runs without its exchange; nothing stands in for it.

Dropless with static shapes: the pairs are sorted by expert, the pairs of
held experts first, and the sorted rows are walked in chunks of
``chunk_rows`` rows: the first chunk always, the others in a loop that
stops after the last chunk with a held pair in it (a trip count known only
on the device, so the walk brings its own backward, a loop of the same
length). So every pair that lands here is computed whatever the routing,
and the work follows the load, a chunk's worth at a time. Within a
chunk the three expert GEMMs are grouped ones (``lax.ragged_dot``, which
the TPU compiler lowers to its own grouped-matmul kernel). Plain XLA: no
Pallas kernel of this repo here yet.
"""

import functools

from ..registry import register


def _count(impl):
    """Trace-time record of which implementation a dispatch took (one per
    traced site, not per step), beside ``attn_kernel_dispatch_total``."""
    from .. import monitor

    monitor.counter(
        "moe_dispatch_total",
        "moe_experts lowerings traced, by implementation (trace-time: one "
        "per traced program, not per step)", labels={"impl": impl}).inc()


def _count_route(scoring):
    from .. import monitor

    monitor.counter(
        "moe_route_dispatch_total",
        "moe_route lowerings traced, by scoring (trace-time: one per "
        "traced site, not per step)", labels={"scoring": scoring}).inc()


@register("moe_route")
def _moe_route(ctx, op):
    """X [..., h], Weight [h, E] -> TopkIds [..., k] (int32) and
    TopkWeights [..., k] (f32): softmax over all E experts in f32 (the
    matmul at ``highest``: a bf16 pass flips near-tied choices), the k
    largest, renormalised to sum 1 where ``norm_topk_prob``.

    ``scoring`` ``sigmoid``: the scores are ``sigmoid(x W)``, each expert
    by itself; an optional ``Bias`` [E] is added for the CHOICE only (the
    k largest of ``score + bias``; the weights are the chosen experts' own
    scores, so the bias carries no gradient), the renormalisation divides
    by ``sum + 1e-20``, and ``routed_scaling_factor`` multiplies the
    weights. Without these three the op is the softmax router it was, to
    the bit."""
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X").astype(jnp.float32)
    w = ctx.get_input(op, "Weight").astype(jnp.float32)
    scoring = op.attr("scoring", "softmax")
    _count_route(scoring)
    logits = jnp.matmul(x, w, precision="highest")
    if scoring == "softmax":
        p = jax.nn.softmax(logits, axis=-1)
        vals, ids = jax.lax.top_k(p, int(op.attr("k")))
        if op.attr("norm_topk_prob", True):
            vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    else:
        assert scoring == "sigmoid", scoring
        p = jax.nn.sigmoid(logits)
        bias = ctx.get_input(op, "Bias")
        choice = p if bias is None else p + jax.lax.stop_gradient(
            bias.astype(jnp.float32))
        _, ids = jax.lax.top_k(choice, int(op.attr("k")))
        vals = jnp.take_along_axis(p, ids, axis=-1)
        if op.attr("norm_topk_prob", True):
            vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    factor = op.attr("routed_scaling_factor", None)
    if factor:
        vals = vals * float(factor)
    ctx.set_output(op, "TopkIds", ids.astype(jnp.int32))
    ctx.set_output(op, "TopkWeights", vals)


def _chunk_rows(route, c, x, w_c, w_gate, w_up, w_down):
    """What chunk ``c`` of the sorted pairs adds: [CH, h] f32, zero in the
    rows past the last held pair."""
    import jax
    import jax.numpy as jnp

    tok, starts, ends, n_here = route
    CH = tok.shape[1]
    lo = c * CH
    sizes = (jnp.clip(ends, lo, lo + CH)
             - jnp.clip(starts, lo, lo + CH)).astype(jnp.int32)
    # The TPU's grouped matmul leaves the rows of no group unwritten, in
    # its result and in its input's gradient alike. SELECT them out of
    # every operand and result (a select's transpose selects too): a stale
    # NaN times a zero weight or a zero cotangent is a NaN in the router's
    # or the experts' gradient.
    valid = ((lo + jnp.arange(CH)) < n_here)[:, None]

    def grouped(lhs, rhs):
        return jnp.where(valid, jax.lax.ragged_dot(lhs, rhs, sizes), 0)

    xs = jnp.where(valid, x[tok[c]], 0)
    y = grouped(jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up), w_down)
    return y.astype(jnp.float32) * w_c[:, None]


def _live_chunks(route):
    """How many chunks hold a held pair (the first counts always)."""
    import jax.numpy as jnp

    tok, _, _, n_here = route
    return jnp.clip(-(-n_here // tok.shape[1]), 1, tok.shape[0])


def _walk_impl(route, x, w_sorted, w_gate, w_up, w_down):
    """The chunks' rows added to their tokens: [T, h] f32. ``route`` is
    ``(tok [n_chunks, CH], starts [E], ends [E], n_here)``, integers all:
    each sorted pair's token, each held expert's span among the sorted
    pairs, and how many pairs landed here."""
    import jax
    import jax.numpy as jnp

    tok = route[0]

    def add_chunk(c, out):
        return out.at[tok[c]].add(
            _chunk_rows(route, c, x, w_sorted[c], w_gate, w_up, w_down))

    out = add_chunk(0, jnp.zeros(x.shape, jnp.float32))
    if tok.shape[0] > 1:
        out = jax.lax.fori_loop(1, _live_chunks(route), add_chunk, out)
    return out


def _walk_fwd(route, x, w_sorted, w_gate, w_up, w_down):
    args = (route, x, w_sorted, w_gate, w_up, w_down)
    return _walk_impl(*args), args


def _walk_bwd(args, d_out):
    """Chunk by chunk, each chunk's rows computed again and transposed;
    the weights' gradients add up in the loop's carry."""
    import jax
    import jax.numpy as jnp

    route, x, w_sorted, w_gate, w_up, w_down = args
    tok = route[0]

    def grads(c):
        _, vjp = jax.vjp(
            lambda x, w_c, *w: _chunk_rows(route, c, x, w_c, *w),
            x, w_sorted[c], w_gate, w_up, w_down)
        return vjp(d_out[tok[c]])

    def add_grads(c, acc):
        g = grads(c)
        return (acc[0] + g[0], acc[1].at[c].set(g[1]),
                acc[2] + g[2], acc[3] + g[3], acc[4] + g[4])

    g = grads(0)
    acc = (g[0], jnp.zeros_like(w_sorted).at[0].set(g[1])) + tuple(g[2:])
    if tok.shape[0] > 1:
        acc = jax.lax.fori_loop(1, _live_chunks(route), add_grads, acc)
    return (None,) + tuple(acc)


@functools.lru_cache(maxsize=None)
def _walk():
    """``_walk_impl`` with its own backward: a loop whose trip count is
    the number of chunks that hold a held pair has no transpose."""
    import jax

    walk = jax.custom_vjp(_walk_impl)
    walk.defvjp(_walk_fwd, _walk_bwd)
    return walk


def moe_experts_dropless(x, ids, wts, w_gate, w_up, w_down, expert_offset,
                         chunk_rows):
    """``sum over held e in a token's choices of wts_e * E_e(x)``, with
    ``E(x) = (silu(x Wg) * (x Wu)) Wd``. x [T, h]; ids, wts [T, k];
    weights [E, h, f], [E, h, f], [E, f, h]. Returns [T, h] in f32."""
    import jax.numpy as jnp

    T = x.shape[0]
    k, E = ids.shape[1], w_gate.shape[0]
    P, CH = T * k, int(chunk_rows)
    n_chunks = -(-P // CH)
    local = ids.reshape(P) - expert_offset
    key = jnp.where((local >= 0) & (local < E), local, E)   # E: not held
    order = jnp.argsort(key, stable=True)
    ends = jnp.cumsum(jnp.bincount(key, length=E + 1)[:E])
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    tail = n_chunks * CH - P
    tok = jnp.pad(order // k, (0, tail)).reshape(n_chunks, CH)
    w_sorted = jnp.pad(wts.reshape(P)[order], (0, tail)).reshape(
        n_chunks, CH)
    return _walk()((tok, starts, ends, ends[-1]), x, w_sorted, w_gate, w_up,
                   w_down)


@register("moe_experts")
def _moe_experts(ctx, op):
    """X [..., h], TopkIds / TopkWeights [..., k] (``moe_route``'s),
    WGate, WUp [E_held, h, f], WDown [E_held, f, h] -> Out [..., h]: the
    held experts' part of the layer's result. ``expert_offset``: the
    first held expert's index among all. The sorted (token, choice) pairs
    are walked a chunk at a time, and the chunk is the whole number of
    token counts next ABOVE an even load: with ``experts_total`` (the
    router's width) the held experts get ``k * E_held / experts_total``
    pairs a token at even routing, so the chunk is ``k * E_held //
    experts_total + 1`` token counts (32 of 512 under top-10: 0.625 pairs
    a token, one token count; 16 of 128 under top-8: 1.0, two - a chunk
    of one would leave a load within a percent of even AT its edge, one
    chunk or two from step to step). One rule for every caller; without
    ``experts_total`` a token count. A load near even takes one chunk."""
    x = ctx.get_input(op, "X")
    ids = ctx.get_input(op, "TopkIds")
    wts = ctx.get_input(op, "TopkWeights")
    lead, h = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, h)
    k = ids.shape[-1]
    _count("ragged_loop")
    T, E = x2.shape[0], ctx.get_input(op, "WGate").shape[0]
    total = int(op.attr("experts_total", 0) or 0)
    chunk_rows = T * (k * E // total + 1 if total else 1)
    out = moe_experts_dropless(
        x2, ids.reshape(-1, k), wts.reshape(-1, k),
        ctx.get_input(op, "WGate"), ctx.get_input(op, "WUp"),
        ctx.get_input(op, "WDown"), int(op.attr("expert_offset", 0)),
        chunk_rows=chunk_rows)
    ctx.set_output(op, "Out", out.astype(x.dtype).reshape(lead + (h,)))
