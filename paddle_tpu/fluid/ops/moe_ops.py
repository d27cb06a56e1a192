"""Sparse-expert ops a Program can reach: the router over ALL experts and
the dropless expert layer over the experts HELD here.

One expert-parallel rank's share (the ``model-configs`` guide's cut): the
router keeps its published width and its experts per token; ``moe_experts``
is told which experts it holds (``expert_offset`` and the leading axis of
its weights), computes their part of the result for the (token, choice)
pairs routed to them, and leaves out what absent experts would add. On one
chip the layer runs without its exchange; nothing stands in for it.

Dropless with static shapes. The (token, choice) pairs of held experts,
expert-major and token-ascending (the order a stable sort of all pairs by
expert would give, so a token's additions keep their order), are walked
in chunks of ``chunk_rows`` rows: the first chunk always, the others in a
loop that stops after the last chunk with a held pair in it (a trip count
known only on the device, so the walk brings its own backward, a loop of
the same length). So every pair that lands here is computed whatever the
routing, and the work follows the load, a chunk's worth at a time. Within
a chunk the three expert GEMMs are grouped ones (``lax.ragged_dot``, which
the TPU compiler lowers to its own grouped-matmul kernel). Plain XLA: no
Pallas kernel of this repo here yet.

**The dispatch plan** - which pairs are held, in what order, which token
and weight each sorted row has, where each held expert's span starts and
ends - is made from the pairs HELD (PR 37; before, a stable ``argsort`` of
all P = T * k pairs, a ``bincount`` and a P-long gather of the weights,
each a scalar at a time on the chip: 2.2-2.8 ms a plan at P = 131-164
thousand, and a P-long scatter in the backward). ``_held_tables`` turns
``TopkIds`` into a 0/1 table ``[E_held, T]`` and the weights into one of
the same shape by compare-and-reduce passes (one fused pass over ``[E_held,
k, T]``), in blocks of 128 tokens with the held pairs counted a block;
``_chunk_plan`` finds a chunk's rows by counting - the blocks that end
before a row, then the lanes of that block's row - and names each row by
its place in the tables, from which its token follows and its weight is
read (a row of the weight table gathered, a lane selected; the backward is
that row gather's scatter-add, CH rows long). Nothing is P-long, nothing is
sorted, and a chunk's plan is made when the walk reaches the chunk.

**Once a layer-step**: the walk's forward rule hands the rows it planned to
its backward, and inside a recomputed segment (``ops/autodiff.py``) they,
the spans and the weight table are marked ``keep_across_recompute(...,
"moe_plan")`` (~2.7 MB a layer at 16,384 tokens, 32 held experts), so the
replay makes no plan; ``moe_route`` keeps the chosen experts' scores and
ids the same way (``"moe_route"``), so the replay runs no ``top_k``.
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


@functools.lru_cache(maxsize=None)
def _chosen(k, n_experts):
    """``(scores, bias or None) -> (the chosen experts' scores, their
    ids)``: the ``k`` largest of ``scores`` (+ ``bias``, for the choice
    only), with a backward of its own so that what it keeps for it is the
    ids it hands out. Those and the chosen scores are marked to be kept
    across a recomputed segment (1.3 MB a layer at 16,384 tokens under
    top-10): the replay then runs neither ``top_k`` (512-way: 1.45 ms a
    layer) nor the sigmoid router's P-long gather of the scores (1.34 ms;
    chip runs, PR 37) - under ``top_k``'s own rule the backward pass reads
    the raw ids, which no mark reaches. The matmul, the scoring and the
    renormalisation are made again. The backward is the gather's
    transpose, as before; ``bias`` gets none."""
    import jax
    import jax.numpy as jnp

    from ...kernels.common import keep_across_recompute

    def choose(scores, bias):
        if bias is None:
            return tuple(jax.lax.top_k(scores, k))
        _, ids = jax.lax.top_k(scores + bias, k)
        return jnp.take_along_axis(scores, ids, axis=-1), ids

    def fwd(scores, bias):
        vals, ids = (keep_across_recompute(v, "moe_route")
                     for v in choose(scores, bias))
        return (vals, ids), ids

    def bwd(ids, cotangents):
        d_vals = cotangents[0]
        _, scatter = jax.vjp(
            lambda s: jnp.take_along_axis(s, ids, axis=-1),
            jnp.zeros(ids.shape[:-1] + (n_experts,), d_vals.dtype))
        return scatter(d_vals)[0], None

    chosen = jax.custom_vjp(choose)
    chosen.defvjp(fwd, bwd)
    return chosen


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
        vals, ids = _chosen(int(op.attr("k")), p.shape[-1])(p, None)
        if op.attr("norm_topk_prob", True):
            vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    else:
        assert scoring == "sigmoid", scoring
        p = jax.nn.sigmoid(logits)
        bias = ctx.get_input(op, "Bias")
        vals, ids = _chosen(int(op.attr("k")), p.shape[-1])(
            p, None if bias is None else bias.astype(jnp.float32))
        if op.attr("norm_topk_prob", True):
            vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    factor = op.attr("routed_scaling_factor", None)
    if factor:
        vals = vals * float(factor)
    ctx.set_output(op, "TopkIds", ids.astype(jnp.int32))
    ctx.set_output(op, "TopkWeights", vals)


# tokens a block of the held table: one row of lanes
_BLOCK = 128


@functools.lru_cache(maxsize=None)
def _once(fn, *static):
    """``fn`` under ``jax.jit`` (``static``: its static arguments' places):
    a step holds the expert layer at a dozen sites - four layers, each in
    the primal lowering, the replay and its checkpointed segment, the
    first chunk and the loop's body apart - and a jitted function is
    walked by Python once a signature, not once a site (~2 s of a cell's
    ``setup_s`` on the chip's host; XLA inlines the calls)."""
    import jax

    return jax.jit(fn, static_argnums=static)


def _held_tables(ids, wts, E, expert_offset):
    """The routing as the held experts see it, by dense passes over
    ``TopkIds`` [T, k] (a token's choices are distinct experts, as
    ``top_k``'s are). Expert-major, token-ascending - the order a stable
    sort of the pairs by expert gives - cut into blocks of ``_BLOCK``
    tokens, row ``e * blocks + t // _BLOCK``, lane ``t % _BLOCK``:
    ``held`` [rows, _BLOCK] (1 where token t chose held expert e),
    ``wtab`` the same shape (that pair's weight, f32, else 0), ``count``
    [rows] (held pairs a block), ``through`` [rows] (held pairs up to and
    with a block: a block's last pair's place among the sorted pairs + 1),
    ``starts``, ``ends`` [E] (each held expert's span among them)."""
    import jax
    import jax.numpy as jnp

    T = ids.shape[0]
    blocks = -(-T // _BLOCK)
    pad = ((0, 0), (0, blocks * _BLOCK - T))
    local = jnp.pad((ids - expert_offset).T, pad, constant_values=-1)
    hit = local[None] == jnp.arange(E, dtype=local.dtype)[:, None, None]
    wtab = jnp.sum(jnp.where(hit, jnp.pad(wts.T, pad)[None], 0), axis=1)
    with jax.named_scope("moe_plan"):
        held = jnp.any(hit, axis=1).astype(jnp.int32).reshape(-1, _BLOCK)
        count = jnp.sum(held, axis=1)
        through = jnp.cumsum(count)
        ends = through.reshape(E, blocks)[:, -1]
        starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    return held, wtab.reshape(-1, _BLOCK), count, through, starts, ends


def _chunk_plan(tables, c, CH):
    """Rows ``c * CH ...`` of the sorted pairs, each as its place in the
    tables (``(e * blocks + t // _BLOCK) * _BLOCK + t % _BLOCK``): the
    blocks that end before the row are counted, that block's row of
    ``held`` is fetched, and the lane is the one where the row's rank
    inside the block is reached. [CH] int32, ascending; past the last
    held pair some place of the last block."""
    import jax
    import jax.numpy as jnp

    held, count, through = tables
    with jax.named_scope("moe_plan"):
        row = c * CH + jnp.arange(CH, dtype=jnp.int32)
        done = through[None, :] <= row[:, None]
        block = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32),
                            held.shape[0] - 1)
        rank = row - jnp.sum(jnp.where(done, count[None, :], 0), axis=1)
        # held pairs up to and with each lane: 0/1 operands, exact
        upto = jnp.dot(
            held[block].astype(jnp.bfloat16),
            jnp.triu(jnp.ones((_BLOCK, _BLOCK), jnp.bfloat16)),
            preferred_element_type=jnp.float32)
        lane = jnp.minimum(
            jnp.sum(upto <= rank.astype(jnp.float32)[:, None], axis=1,
                    dtype=jnp.int32), _BLOCK - 1)
        return block * _BLOCK + lane


def _row_tokens(route, c, x, wtab):
    """Chunk ``c``'s rows' tokens, from their places in the tables (an
    expert's part of a table is the tokens padded to whole blocks)."""
    import jax.numpy as jnp

    place, _, ends, _ = route
    return jnp.minimum(place[c] % (wtab.size // ends.shape[0]),
                       x.shape[0] - 1)


def _chunk_rows(route, c, x, wtab, w_gate, w_up, w_down):
    """What chunk ``c`` of the sorted pairs adds: [CH, h] f32, zero in the
    rows past the last held pair."""
    import jax
    import jax.numpy as jnp

    place, starts, ends, n_here = route
    CH = place.shape[1]
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

    xs = jnp.where(valid, x[_row_tokens(route, c, x, wtab)], 0)
    y = grouped(jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up), w_down)
    # a row's weight: its block's row of the table, then its lane
    lanes = jnp.arange(_BLOCK, dtype=jnp.int32)[None, :]
    w_c = jnp.sum(jnp.where(lanes == (place[c] % _BLOCK)[:, None],
                            wtab[place[c] // _BLOCK], 0), axis=1)
    return y.astype(jnp.float32) * w_c[:, None]


def _live_chunks(route):
    """How many chunks hold a held pair (the first counts always)."""
    import jax.numpy as jnp

    place, _, _, n_here = route
    return jnp.clip(-(-n_here // place.shape[1]), 1, place.shape[0])


def _walk_planned(route, tables, x, wtab, w_gate, w_up, w_down):
    """The chunks' rows added to their tokens, [T, h] f32, and the plan
    as the walk made it: ``route`` is ``(place [n_chunks, CH], starts [E],
    ends [E], n_here)``, integers all - each sorted pair's place in the
    tables (which names its token and its weight), each held expert's span
    among the sorted pairs, how many pairs landed here. ``place`` comes in
    empty; a chunk's rows are written when the walk reaches it, so the
    plan's cost follows the chunks walked."""
    import jax
    import jax.numpy as jnp

    def add_chunk(c, carry):
        out, place = carry
        place = place.at[c].set(
            _once(_chunk_plan, 2)(tables, c, place.shape[1]))
        planned = (place,) + tuple(route[1:])
        return out.at[_row_tokens(planned, c, x, wtab)].add(
            _once(_chunk_rows)(planned, c, x, wtab, w_gate, w_up,
                               w_down)), place

    carry = add_chunk(0, (jnp.zeros(x.shape, jnp.float32), route[0]))
    if route[0].shape[0] > 1:
        carry = jax.lax.fori_loop(1, _live_chunks(route), add_chunk, carry)
    return carry


def _walk_impl(*args):
    return _walk_planned(*args)[0]


def _walk_fwd(route, tables, x, wtab, w_gate, w_up, w_down):
    """The backward pass walks by the plan the forward one made: inside a
    recomputed segment it is kept across the boundary with the weight
    table (0.5-0.7 MB a layer of rows, 0.5-2 MB of table) and the replay
    builds none."""
    from ...kernels.common import keep_across_recompute

    out, place = _walk_planned(route, tables, x, wtab, w_gate, w_up, w_down)
    place, starts, ends, n_here, wtab = (
        keep_across_recompute(r, "moe_plan")
        for r in (place,) + tuple(route[1:]) + (wtab,))
    return out, ((place, starts, ends, n_here), x, wtab, w_gate, w_up,
                 w_down)


def _walk_bwd(args, d_out):
    """Chunk by chunk, each chunk's rows computed again and transposed;
    the gradients of the weight table and of the experts' weights add up
    in the loop's carry."""
    import jax

    route, x, wtab, w_gate, w_up, w_down = args

    def grads(c):
        _, vjp = jax.vjp(
            lambda x, wtab, *w: _once(_chunk_rows)(route, c, x, wtab, *w),
            x, wtab, w_gate, w_up, w_down)
        return vjp(d_out[_row_tokens(route, c, x, wtab)])

    def add_grads(c, acc):
        return tuple(a + g for a, g in zip(acc, grads(c)))

    acc = grads(0)
    if route[0].shape[0] > 1:
        acc = jax.lax.fori_loop(1, _live_chunks(route), add_grads, acc)
    return (None, None) + tuple(acc)


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

    T, k = ids.shape
    E, CH = w_gate.shape[0], int(chunk_rows)
    held, wtab, count, through, starts, ends = _once(_held_tables, 2, 3)(
        ids, wts, E, expert_offset)
    route = (jnp.zeros((-(-T * k // CH), CH), jnp.int32), starts, ends,
             ends[-1])
    return _walk()(route, (held, count, through), x, wtab, w_gate, w_up,
                   w_down)


@register("moe_experts")
def _moe_experts(ctx, op):
    """X [..., h], TopkIds / TopkWeights [..., k] (``moe_route``'s),
    WGate, WUp [E_held, h, f], WDown [E_held, f, h] -> Out [..., h]: the
    held experts' part of the layer's result. ``expert_offset``: the
    first held expert's index among all. The held (token, choice) pairs
    in expert-major order (the module's docstring: planned from the pairs
    held, a chunk at a time, and once a layer-step under recomputation;
    ``moe_dispatch_total{impl="ragged_loop_held"}``) are walked a chunk at
    a time, and the chunk is the whole number of
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
    _count("ragged_loop_held")
    T, E = x2.shape[0], ctx.get_input(op, "WGate").shape[0]
    total = int(op.attr("experts_total", 0) or 0)
    chunk_rows = T * (k * E // total + 1 if total else 1)
    out = moe_experts_dropless(
        x2, ids.reshape(-1, k), wts.reshape(-1, k),
        ctx.get_input(op, "WGate"), ctx.get_input(op, "WUp"),
        ctx.get_input(op, "WDown"), int(op.attr("expert_offset", 0)),
        chunk_rows=chunk_rows)
    ctx.set_output(op, "Out", out.astype(x.dtype).reshape(lead + (h,)))
