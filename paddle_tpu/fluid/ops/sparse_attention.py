"""Learned sparse attention's selection: the lightning indexer of
DeepSeek-V3.2-Exp's sparse attention (DeepSeek-AI 2025), as a Program op.

Per batch row, with ``Hi`` indexer heads of width ``di`` and ONE shared
indexer key head::

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s]),   s <= t
    S_t     = every s <= t with I[t, s] >= the topk-th largest of row t

so ``S_t`` is the ``min(t + 1, topk)`` keys of largest score, and on exact
ties at the boundary every tied key (top-k's own choice among equals is
arbitrary; this rule is not). ``sparse_index`` gives ``S_t`` as a mask
``Select`` [B, S, S] of one byte an entry (1 = query t may see key s, the
causal triangle included), which ``fused_multihead_attention`` takes as
its ``Select`` input. The mask carries no gradient.

Scores are made ``chunk_size`` query rows at a time (``lax.map``, in
``_GROUPS`` groups of chunks, each against the keys its last row can see),
so no [S, S] float32 outlives its chunk: the operands in their own type (bf16
under AMP), accumulation, the weighted sum over heads and the comparison
against the threshold in f32. The threshold is exact, never
``approx_max_k``: the set is the model. It is found by bisection on the
scores' bits (``kth_largest``: 32 counting passes over a chunk; on a v5e
0.47 ms a chunk of [512, 16384] against 4.8 ms for ``lax.top_k`` and 5.4 ms
for a sort, PR 32's chip run). Plain XLA: no Pallas kernel of this repo
here yet.
"""

import functools

from ..registry import register

# The query chunks are walked in this many groups of consecutive chunks,
# each against the keys its last row can see: 4 groups score 10 sixteenths
# of the [S, S] square, not all of it.
_GROUPS = 4


def _count(impl):
    """Trace-time record of what finds the threshold (one per traced site,
    not per step), beside ``gdn_dispatch_total``."""
    from .. import monitor

    monitor.counter(
        "sparse_index_dispatch_total",
        "sparse_index lowerings traced, by what finds a row's threshold "
        "(trace-time: one per traced program, not per step)",
        labels={"impl": impl}).inc()


def index_scores(q, k, w):
    """q [B, Hi, T, di], k [B, S, di], w [B, T, Hi] -> I [B, T, S] f32."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bhtd,bsd->bhts", q, k, precision="highest",
                   preferred_element_type=jnp.float32)
    w = jnp.swapaxes(w.astype(jnp.float32), 1, 2)[..., None]
    return jnp.sum(jax.nn.relu(s) * w, axis=1)


def kth_largest(x, k):
    """The ``k``-th largest of each row of ``x`` [..., n] f32, exactly
    (-inf where fewer than ``k`` entries lie above -inf): a float's bits,
    with the lower 31 flipped where the sign is set, order as the floats
    do, so the largest threshold that at least ``k`` entries reach is built
    bit by bit from the top, one counting pass over the row a bit."""
    import jax
    import jax.numpy as jnp

    low31, top = jnp.int32(0x7FFFFFFF), jnp.int32(-2 ** 31)

    def flip(b):        # bits <-> ordered key; its own inverse
        return jnp.where(b < 0, b ^ low31, b)

    key = flip(jax.lax.bitcast_convert_type(x, jnp.int32))

    def bit(i, t):      # t: the threshold so far, offset by 2^31
        cand = t | (jnp.int32(1) << (31 - i))
        n = jnp.sum((key >= (cand ^ top)[..., None]).astype(jnp.int32), -1)
        return jnp.where(n >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros(x.shape[:-1], jnp.int32))
    # no bit taken: not even k entries in the row (n < k)
    return jnp.where(t == 0, -jnp.inf, jax.lax.bitcast_convert_type(
        flip(t ^ top), jnp.float32))


def sparse_index_select(q, k, w, topk, chunk_size=512):
    """The mask [B, S, S] int8 of ``S_t`` (module docstring)."""
    import jax
    import jax.numpy as jnp

    from ...kernels.attention import count_select_pairs

    B, Hi, S, di = q.shape
    count_select_pairs(S, topk)
    cols = jnp.arange(S)
    if topk >= S:       # every causal key is kept: nothing to score
        _count("dense")
        return jnp.broadcast_to(cols[None, :] <= cols[:, None],
                                (B, S, S)).astype(jnp.int8)
    _count("bisect")
    C = max(c for c in range(1, min(chunk_size, S) + 1) if S % c == 0)
    n = S // C
    groups = max(g for g in range(1, min(_GROUPS, n) + 1) if n % g == 0)
    per = n // groups * C       # query rows a group

    def chunk(keys, args):
        qc, wc, row0 = args                 # [B, Hi, C, di], [B, C, Hi]
        valid = cols[None, :keys] <= (row0 + jnp.arange(C))[:, None]
        scores = jnp.where(valid, index_scores(qc, k[:, :keys], wc),
                           -jnp.inf)
        # a row of fewer than topk causal keys reads kth = -inf: all kept
        kth = kth_largest(scores, topk)[..., None]
        return jnp.logical_and(valid, scores >= kth).astype(jnp.int8)

    def chunks(x, axis):    # a group's rows on ``axis`` -> [per / C, ..., C]
        return jnp.moveaxis(x.reshape(
            x.shape[:axis] + (per // C, C) + x.shape[axis + 1:]), axis, 0)

    parts = []
    for g in range(groups):
        # the group's last row sees keys 0 .. (g + 1) * per - 1: the keys
        # after them are scored by no chunk of it
        rows, keys = slice(g * per, (g + 1) * per), (g + 1) * per
        out = jax.lax.map(functools.partial(chunk, keys), (
            chunks(q[:, :, rows], 2), chunks(w[:, rows], 1),
            jnp.arange(g * per, (g + 1) * per, C)))
        parts.append(jnp.pad(jnp.moveaxis(out, 0, 1).reshape(B, per, keys),
                             ((0, 0), (0, 0), (0, S - keys))))
    return jnp.concatenate(parts, axis=1)


@register("sparse_index")
def _sparse_index(ctx, op):
    """Q [B, Hi, S, di], K [B, S, di], W [B, S, Hi] -> Select [B, S, S]
    int8 (module docstring); attrs ``topk``, ``chunk_size``."""
    import jax

    from ...kernels.common import keep_across_recompute

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    w = ctx.get_input(op, "W")
    select = jax.lax.stop_gradient(sparse_index_select(
        q, k, w, int(op.attr("topk")), int(op.attr("chunk_size", 512))))
    # 32 counting passes a chunk for a byte an entry: kept, not made again
    ctx.set_output(op, "Select", keep_across_recompute(select,
                                                       "sparse_index"))
