"""The chunked gated delta rule as Pallas kernels, forward and backward.

The mathematics and the precisions are those of
``fluid/ops/linear_attention.py:gated_delta_rule_chunked`` (which stays
the fallback and the oracle): per value head and chunk of C positions,
with ``gc`` the running sum of the log decay inside the chunk and ``S``
the ``[dk, dv]`` state that enters the chunk::

    A = tril((k beta) k^T * exp(gc_i - gc_j), -1);  T = (I + A)^-1
    u = T (v beta);  w = T (k beta exp(gc));  v_new = u - w S
    o = (q exp(gc)) S + tril(q k^T * exp(gc_i - gc_j)) v_new
    S <- S exp(gc_last) + (k exp(gc_last - gc))^T v_new

Matmul operands go in v's dtype with f32 accumulation; the decays, the
triangular system (its matmuls f32-exact) and the carried state are f32.

What the kernels change is where a chunk's ``[C, C]`` and ``[C, d]``
objects live: in VMEM. A grid step owns one (batch, ``KEY_HEADS`` key
heads, tile of ``TILE`` positions) and with it those heads' value heads
(Hv / Hk each, which share a key head's q and k); the sequence axis is
sequential and the states are a VMEM scratch zeroed at a row's first tile.
Inside a tile the kernel walks GROUPS of ``ROWS`` = 128 positions: the
128 / C chunks of a group share every matmul that does not touch the
state, as one block-diagonal ``[128, 128]`` problem (the off-diagonal
blocks are masked to zero, so the chunk size of the equations is still C),
which is the MXU's shape; the matmuls with the state run chunk by chunk. A
value head's group is one long chain of dependent steps, so the step's
value heads go through every stage side by side: a neighbour's stage is
what the units do while one waits. q, k and v are read straight from the
projection's layout (``[B, S, H * d]``, a ``(1, TILE, d)`` block at column
``h``), and where the caller asks, q and k are L2-normalised on the rows in
VMEM: in XLA that norm and its backward cost more passes over q and k than
the delta rule's own traffic.

The backward kernel walks the tiles and groups in reverse with ``dS``
carried in VMEM. The forward it differentiates kept, a group, the state
that entered it and its chunks' inverses (both f32); the rest of the group
is recomputed and ``jax.vjp`` of the same group function gives the
cotangents, so the derivation is the forward's own, and dq and dk arrive
summed over the value heads of a key head. Inside a recomputed segment
``o``, the states and the inverses are marked to be kept across it
(``common.keep_across_recompute``): the replay then has no use for the
forward kernel, which runs once a layer a step, and the backward reads
what the primal pass made.

THE CHANNEL-GATED RULE (Kimi Delta Attention: ``kda_chunk_fwd``,
``kda_chunk_bwd``; ``gated_delta_rule_pallas`` takes it where g comes ``[B,
S, H, dk]``, a log decay a key CHANNEL, and the scalar rule where g comes
``[B, S, Hv]``). The state decays row by row, ``S' = Diag(exp(g_t)) S``, so
every decay sits INSIDE a contraction over dk (``Gc`` [C, dk] the running
sum of g inside the chunk)::

    A = tril((k beta exp(Gc)) (k exp(-Gc))^T, -1);  T = (I + A)^-1
    v_new = T (v beta) - T (k beta exp(Gc)) S
    o = (q exp(Gc)) S + tril((q exp(Gc)) (k exp(-Gc))^T) v_new
    S <- Diag(exp(Gc_last)) S + (k exp(Gc_last - Gc))^T v_new

``exp(-Gc)`` alone overflows float32 (g reaches -1.6 a position: -100 over
a chunk), and no ``[C, C]`` decay matrix exists to multiply scores by. The
pairwise decay ``exp(Gc_i - Gc_j)``, i > j, is formed in LEVELS, never in
its two halves: positions i > j of a chunk differ first at one bit; at
that bit's block size b (1, 2, .. C/2) i lies in an odd block and j in the
even block before it, and with r the odd block's first row ``exp(Gc_i -
Gc_j) = exp(Gc_i - Gc_r) exp(Gc_r - Gc_j)``, both factors <= 1. A level is
one matmul pair over the whole group, ``(x E_b) (k E_b)^T`` with ``E_b``
[ROWS, dk] the factor of a row's side, kept where its mask says (odd-block
row, its even sibling's column); the levels' masks tile the strict lower
triangle of a chunk exactly once, and the diagonal of the q k product
carries no decay. Nothing clamps g. The running sum Gc and the rows of Gc
a level needs are f32-exact matmuls with 0/1 matrices (the MXU is idle
meanwhile), so g comes as projected, ``[B, S, H * dk]`` f32, and dg leaves
the same way. The state is carried TRANSPOSED, ``[dv, dk]``: its decay is
then a row times its rows. Hk = Hv here. Everything else - the group of
128 positions, the inversion, the heads side by side, the backward from
the forward's own group function with the entering state and the inverse
kept - is the scalar rule's.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (interpret, keep_across_recompute, named_pallas_call,
                     supports_pallas)

# the name of every ``pallas_call`` here: never ``attn_*`` (the attention
# metrics read that prefix)
KERNEL_NAMES = ("gdn_chunk_fwd", "gdn_chunk_bwd", "kda_chunk_fwd",
                "kda_chunk_bwd")

ROWS = 128      # positions a group: the chunks that share a matmul
TILE = 1024     # positions a grid step (8 groups: one f32 tile of gc rows)
KEY_HEADS = 2   # key heads a grid step, where their number is even
_BASE = 16      # diagonal blocks inverted by elimination on the VPU

_F32 = jnp.float32


def supported(dk, dv, chunk_size):
    """The shapes that fill the kernels' tiles, either rule's: head dims in
    whole lane tiles and a chunk that packs into a 128-row group in whole
    bf16 sublane tiles. Anything else runs the XLA chunked form."""
    return (supports_pallas() and dk % 128 == 0 and dv % 128 == 0
            and chunk_size % 16 == 0 and ROWS % chunk_size == 0)


# -- a group of a step's heads: pure, on VMEM-resident values -------------------
def _dot(a, b, dims, exact):
    return lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=_F32,
        precision=lax.Precision.HIGHEST if exact else None)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_with_vjp(a, b, dims):
    """``a . b`` contracted over ``dims``, operands as they come (v's
    dtype), f32 out. Its backward casts the cotangent to the operands'
    dtype first, so a backward matmul is what a forward one is."""
    return _dot(a, b, dims, a.dtype == _F32)


def _mm_fwd(a, b, dims):
    return _mm_with_vjp(a, b, dims), (a, b)


def _mm_bwd(dims, res, g):
    a, b = res
    g = g.astype(a.dtype)
    exact = a.dtype == _F32
    if dims == _NN:
        da, db = _dot(g, b, _NT, exact), _dot(a, g, _TN, exact)
    elif dims == _NT:
        da, db = _dot(g, b, _NN, exact), _dot(g, a, _TN, exact)
    else:
        assert dims == _TN, dims
        da, db = _dot(b, g, _NT, exact), _dot(a, g, _NN, exact)
    return da.astype(a.dtype), db.astype(b.dtype)


_mm_with_vjp.defvjp(_mm_fwd, _mm_bwd)


def _masks(C):
    """The ``[ROWS, ROWS]`` masks of a group, from ``iota``."""
    row = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    col = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    same = (row // C) == (col // C)
    m = {"eye": row == col, "lower": same & (row >= col),
         "strict": same & (row > col),
         "last": col == (row // C) * C + (C - 1),
         "base": (row // _BASE) == (col // _BASE),
         # column j of a row's own base block, j = 0 .. _BASE - 2
         "own": col - (row // _BASE) * _BASE}
    size = _BASE
    while size < C:     # the block below the diagonal that a merge adds
        m["merge%d" % size] = ((row // (2 * size)) == (col // (2 * size))) \
            & ((row // size) != (col // size))
        size *= 2
    return m


def _inverses(a, C, m):
    """``(I + a)^-1`` of each ``a`` of the list (one a value head),
    strictly lower inside each chunk of the group (block diagonal), f32.
    The ``_BASE`` blocks by elimination, a column at a time (``T <- T -
    a[:, j] T[j, :]``), all blocks at once; then ``inv([[p, 0], [c, d]]) =
    [[ip, 0], [-id c ip, id]]``, every pair of the group in one f32-exact
    matmul pair a level. The heads go through every step side by side:
    each is one long chain of dependent steps, and a neighbour's step is
    what the units can do meanwhile."""
    a0 = [jnp.where(m["base"], x, 0.0) for x in a]
    t = [m["eye"].astype(_F32) for _ in a]
    blocks = (ROWS // _BASE, _BASE, ROWS)
    for j in range(min(_BASE, C) - 1):
        col = [jnp.sum(jnp.where(m["own"] == j, x, 0.0), axis=1,
                       keepdims=True) for x in a0]         # a0[r, blk(r) + j]
        row = [jnp.broadcast_to(x.reshape(blocks)[:, j:j + 1, :],
                                blocks).reshape(ROWS, ROWS)
               for x in t]                                 # t[blk(r) + j, :]
        t = [x - c * r for x, c, r in zip(t, col, row)]
    size = _BASE
    while size < C:
        c = [jnp.where(m["merge%d" % size], x, 0.0) for x in a]
        tc = [_dot(x, y, _NN, True) for x, y in zip(t, c)]
        t = [x - _dot(y, x, _NN, True) for x, y in zip(t, tc)]
        size *= 2
    return t


def _column(x_row, mask):
    """``[ROWS, 1]``: of each row of the mask, the entry of ``x_row`` [1,
    ROWS] that it picks."""
    return jnp.sum(jnp.where(mask, x_row, 0.0), axis=1, keepdims=True)


def _saved_inverse(t, m):
    """``a -> (I + a)^-1`` where the forward kept the answer ``t``: the
    backward walk does not invert again, it only applies the inverse's
    two-matmul backward rule."""

    @jax.custom_vjp
    def inv(a):
        return t

    def bwd(_, dt):
        # d(inv) = -inv dM inv; only the strict lower part of a chunk varies
        da = -_dot(_dot(t, dt, _TN, True), t, _NT, True)
        return (jnp.where(m["strict"], da, 0.0),)

    inv.defvjp(lambda a: (t, None), bwd)
    return inv


def _mm_of(inverses):
    """The group functions' matmul: plain in the forward kernel; under
    ``jax.vjp`` (``inverses`` given) the one that brings its own backward.
    A ``custom_vjp`` that nothing differentiates would reach Mosaic as a
    call it cannot lower, so the forward kernel gets none."""
    if inverses is None:
        return lambda a, b, dims: _dot(a, b, dims, a.dtype == _F32)
    return _mm_with_vjp


def _normalised(qf, kf, dk, cd, eps):
    """``(qf, kf, q, k)`` of the heads' f32 rows (lists, [ROWS, dk] each):
    L2-normalised over the head dim where ``eps`` is given (q also scaled
    by ``dk ** -0.5``), rounded to ``cd`` as the caller of the XLA form
    rounds them, and the f32 images of what the matmuls take."""
    if eps is not None:
        qf = [t * lax.rsqrt(jnp.sum(t * t, 1, keepdims=True) + eps)
              * dk ** -0.5 for t in qf]
        kf = [t * lax.rsqrt(jnp.sum(t * t, 1, keepdims=True) + eps)
              for t in kf]
    q, k = ([t.astype(cd) for t in ts] for ts in (qf, kf))
    if eps is not None:     # the f32 images of what the matmuls take
        qf, kf = ([t.astype(_F32) for t in ts] for ts in (q, k))
    return qf, kf, q, k


def _stack(xs, axis):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=axis)


def _key_heads(qf, kf, vf, gcs, betas, states, *, dk, cd, C, m, eps,
               inverses=None):
    """One group of ``ROWS // C`` chunks of a grid step's KEY heads: the
    ``len(states)`` value heads go through every stage side by side, and
    those of one key head share its q and k. qf, kf [ROWS, key heads * dk]
    f32 images of the ``cd`` inputs (their cotangents then sum in f32),
    L2-normalised here over each head's dim (q also scaled by ``dk **
    -0.5``) where ``eps`` is given, and rounded to ``cd`` as the caller of
    the XLA form rounds them; vf [ROWS, heads * dv] f32; gcs and betas [1,
    ROWS] f32 rows and states [dk, dv] f32, one a value head -> ((o [ROWS,
    heads * dv] f32, the states after the group), the chunks' inverses
    [ROWS, ROWS] f32).

    ``inverses`` (the forward's, kept) are given under ``jax.vjp``: the
    matmuls and the inversion then carry their own backward rules
    (``_mm_of``)."""
    heads = range(len(states))
    dv = vf.shape[1] // len(states)
    rep = len(states) // (qf.shape[1] // dk)    # value heads a key head
    _mm = _mm_of(inverses)
    qf, kf = ([t[:, i * dk:(i + 1) * dk] for i in range(len(states) // rep)]
              for t in (qf, kf))
    qf, kf, q, k = _normalised(qf, kf, dk, cd, eps)
    qk = [_mm(q[i], k[i], _NT) for i in range(len(q))]  # value heads share it
    # from here on, per value head
    qf, kf, q, k, qk = ([ts[j // rep] for j in heads]
                        for ts in (qf, kf, q, k, qk))
    g_col = [_column(gcs[j], m["eye"]) for j in heads]
    b_col = [_column(betas[j], m["eye"]) for j in heads]
    # gc at the end of the row's chunk
    g_last = [_column(gcs[j], m["last"]) for j in heads]
    decay = [jnp.where(m["lower"], jnp.exp(
        jnp.where(m["lower"], g_col[j] - gcs[j], 0.0)), 0.0) for j in heads]
    k_beta = [kf[j] * b_col[j] for j in heads]
    a = [jnp.where(m["strict"],
                   _mm(k_beta[j].astype(cd), k[j], _NT) * decay[j], 0.0)
         for j in heads]
    if inverses is None:
        t32 = _inverses(a, C, m)
    else:
        t32 = [_saved_inverse(inverses[j], m)(a[j]) for j in heads]
    t = [x.astype(cd) for x in t32]
    e_g = [jnp.exp(g_col[j]) for j in heads]
    u = [_mm(t[j], (vf[:, j * dv:(j + 1) * dv] * b_col[j]).astype(cd), _NN)
         for j in heads]
    w = [_mm(t[j], (k_beta[j] * e_g[j]).astype(cd), _NN).astype(cd)
         for j in heads]
    q_g = [(qf[j] * e_g[j]).astype(cd) for j in heads]
    k_dec = [(kf[j] * jnp.exp(g_last[j] - g_col[j])).astype(cd)
             for j in heads]
    e_last = [jnp.exp(g_last[j]) for j in heads]
    states = list(states)
    v_new, o_state = [[] for _ in heads], [[] for _ in heads]
    for c in range(ROWS // C):
        rows = slice(c * C, (c + 1) * C)
        s_cd = [states[j].astype(cd) for j in heads]
        v_c = [u[j][rows] - _mm(w[j][rows], s_cd[j], _NN) for j in heads]
        for j in heads:
            o_state[j].append(_mm(q_g[j][rows], s_cd[j], _NN))
            v_new[j].append(v_c[j])
        states = [states[j] * e_last[j][c * C:c * C + 1] + _mm(
            k_dec[j][rows], v_c[j].astype(cd), _TN) for j in heads]
    o = [_stack(o_state[j], 0) + _mm((qk[j] * decay[j]).astype(cd),
                                     _stack(v_new[j], 0).astype(cd), _NN)
         for j in heads]
    return (_stack(o, 1), states), t32


def _kda_masks(C, dk):
    """``_masks`` and, for each level b = 1, 2, .. C/2 of the pairwise
    decay: ``pair<b>`` (row in an odd block of b, column in the even block
    before it), ``ref<b>`` (f32 0/1: the column that is the first row of
    the odd block of a row's pair) and ``odd<b>`` [ROWS, dk] (the row lies
    in an odd block); ``sum`` and ``end`` are ``lower`` and ``last`` as f32
    0/1 matrices (the running sum inside a chunk; a chunk's last row)."""
    m = _masks(C)
    row = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    col = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    wide = lax.broadcasted_iota(jnp.int32, (ROWS, dk), 0)
    m["sum"], m["end"] = m["lower"].astype(_F32), m["last"].astype(_F32)
    b = 1
    while b < C:
        odd = lambda x: (x // b) - (x // (2 * b)) * 2 == 1    # noqa: E731
        m["pair%d" % b] = ((row // (2 * b)) == (col // (2 * b))) \
            & odd(row) & ~odd(col)
        m["ref%d" % b] = (col == (row // (2 * b)) * (2 * b) + b).astype(_F32)
        m["odd%d" % b] = odd(wide)
        b *= 2
    return m


def _kda_heads(qf, kf, vf, gf, betas, states, *, dk, cd, C, m, eps,
               inverses=None):
    """``_key_heads`` for the channel-gated rule (the header's equations):
    one group of a grid step's heads, side by side. qf, kf, gf [ROWS, heads
    * dk] f32 (g the log decay a channel, as projected: the running sum is
    made here), vf [ROWS, heads * dv] f32, betas [1, ROWS] rows, states
    [dv, dk] f32 (TRANSPOSED), one a head -> ((o [ROWS, heads * dv] f32,
    the states after the group), the chunks' inverses)."""
    heads = range(len(states))
    dv = vf.shape[1] // len(states)
    _mm = _mm_of(inverses)
    qf, kf, gf = ([t[:, j * dk:(j + 1) * dk] for j in heads]
                  for t in (qf, kf, gf))
    qf, kf, _, _ = _normalised(qf, kf, dk, cd, eps)
    gc = [_mm(m["sum"], gf[j], _NN) for j in heads]         # [ROWS, dk]
    b_col = [_column(betas[j], m["eye"]) for j in heads]
    k_beta = [kf[j] * b_col[j] for j in heads]
    # the pairwise decays, level by level: both factors <= 1
    a = [jnp.zeros((ROWS, ROWS), _F32) for _ in heads]
    qk = [jnp.where(m["eye"], jnp.sum(qf[j] * kf[j], 1, keepdims=True), 0.0)
          for j in heads]       # the diagonal carries no decay
    b = 1
    while b < C:
        pair, odd = m["pair%d" % b], m["odd%d" % b]
        d = [gc[j] - _mm(m["ref%d" % b], gc[j], _NN) for j in heads]
        e = [jnp.exp(jnp.where(odd, d[j], -d[j])) for j in heads]
        ke = [(kf[j] * e[j]).astype(cd) for j in heads]
        a = [a[j] + jnp.where(pair, _mm((k_beta[j] * e[j]).astype(cd),
                                        ke[j], _NT), 0.0) for j in heads]
        qk = [qk[j] + jnp.where(pair, _mm((qf[j] * e[j]).astype(cd), ke[j],
                                          _NT), 0.0) for j in heads]
        b *= 2
    if inverses is None:
        t32 = _inverses(a, C, m)
    else:
        t32 = [_saved_inverse(inverses[j], m)(a[j]) for j in heads]
    t = [x.astype(cd) for x in t32]
    e_g = [jnp.exp(gc[j]) for j in heads]
    u = [_mm(t[j], (vf[:, j * dv:(j + 1) * dv] * b_col[j]).astype(cd), _NN)
         for j in heads]
    w = [_mm(t[j], (k_beta[j] * e_g[j]).astype(cd), _NN).astype(cd)
         for j in heads]
    q_g = [(qf[j] * e_g[j]).astype(cd) for j in heads]
    # Gc at the end of the row's chunk, on every row
    g_last = [_mm(m["end"], gc[j], _NN) for j in heads]
    k_dec = [(kf[j] * jnp.exp(g_last[j] - gc[j])).astype(cd) for j in heads]
    states = list(states)
    v_new, o_state = [[] for _ in heads], [[] for _ in heads]
    for c in range(ROWS // C):
        rows = slice(c * C, (c + 1) * C)
        s_cd = [states[j].astype(cd) for j in heads]
        v_c = [u[j][rows] - _mm(w[j][rows], s_cd[j], _NT) for j in heads]
        for j in heads:
            o_state[j].append(_mm(q_g[j][rows], s_cd[j], _NT))
            v_new[j].append(v_c[j])
        states = [states[j] * jnp.exp(gc[j][(c + 1) * C - 1:(c + 1) * C])
                  + _mm(v_c[j].astype(cd), k_dec[j][rows], _TN)
                  for j in heads]
    o = [_stack(o_state[j], 0) + _mm(qk[j].astype(cd),
                                     _stack(v_new[j], 0).astype(cd), _NN)
         for j in heads]
    return (_stack(o, 1), states), t32


def _rule(channel, state, g_ref, C):
    """``(group function, masks, dk, gate(r, rows))`` of the rule: the
    scalar rule's gate is a row [1, ROWS] a value head of the running sum,
    the channel-gated rule's the [ROWS, heads * dk] log decays themselves;
    its state is carried transposed."""
    heads = range(state.shape[0])
    if channel:
        dk = state.shape[2]
        return (_kda_heads, _kda_masks(C, dk), dk,
                lambda r, rows: g_ref[0, rows, :])
    return (_key_heads, _masks(C), state.shape[1],
            lambda r, rows: [g_ref[0, j, pl.ds(r, 1), :] for j in heads])


def _fwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, o_ref, *rest, C,
                groups, eps, save, channel=False):
    s_ref, t_ref, state = rest if save else (None, None) + rest
    heads = range(state.shape[0])

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    rule, m, width, gate = _rule(channel, state, gc_ref, C)

    def group(r, s_in):
        rows = pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)
        (o, s_out), t = rule(
            q_ref[0, rows, :].astype(_F32), k_ref[0, rows, :].astype(_F32),
            v_ref[0, rows, :].astype(_F32), gate(r, rows),
            [beta_ref[0, j, pl.ds(r, 1), :] for j in heads], s_in,
            dk=width, cd=v_ref.dtype, C=C, m=m, eps=eps)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        if save:
            for j in heads:
                s_ref[0, j, r] = s_in[j]
                t_ref[0, j, r] = t[j]
        return s_out

    out = lax.fori_loop(0, groups, group, [state[j] for j in heads])
    for j in heads:
        state[j] = out[j]


def _bwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, s_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgc_ref, dbeta_ref, dstate, *, C,
                groups, eps, channel=False):
    heads = range(dstate.shape[0])

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    rule, m, width, gate = _rule(channel, dstate, gc_ref, C)

    def group(i, ds_out):
        r = groups - 1 - i      # the tile's groups in reverse
        rows = pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)
        _, vjp, _ = jax.vjp(
            functools.partial(rule, dk=width, cd=v_ref.dtype, C=C, m=m,
                              eps=eps,
                              inverses=[t_ref[0, j, r] for j in heads]),
            q_ref[0, rows, :].astype(_F32), k_ref[0, rows, :].astype(_F32),
            v_ref[0, rows, :].astype(_F32), gate(r, rows),
            [beta_ref[0, j, pl.ds(r, 1), :] for j in heads],
            [s_ref[0, j, r] for j in heads], has_aux=True)
        # the value heads' shares of dq and dk arrive summed, in f32
        dq, dk, dv, dgc, dbeta, ds_in = vjp(
            (do_ref[0, rows, :].astype(_F32), ds_out))
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        if channel:
            dgc_ref[0, rows, :] = dgc
        for j in heads:
            if not channel:
                dgc_ref[0, j, pl.ds(r, 1), :] = dgc[j]
            dbeta_ref[0, j, pl.ds(r, 1), :] = dbeta[j]
        return ds_in

    out = lax.fori_loop(0, groups, group, [dstate[j] for j in heads])
    for j in heads:
        dstate[j] = out[j]


# the backward step's blocks (two key heads, their four value heads, twice
# for the pipeline) pass the 16 MiB a kernel gets by default; the chip has
# 128 MiB of VMEM
_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"),
               vmem_limit_bytes=64 * 1024 * 1024)


@functools.lru_cache(maxsize=None)
def _calls(B, Sp, Hk, Hv, dtype, dk, dv, C, eps, tile, kh, interpreted,
           channel=False):
    """The ``pallas_call``s of one input signature - ``fwd``, ``fwd_keep``
    (the forward that also keeps what the backward needs) and ``bwd`` -
    built ONCE.
    ``pl.pallas_call`` hands back a jit that caches its trace on itself:
    asked for the same call again, the second and third DeltaNet layer,
    shape inference, the primal lowering and the ``autodiff`` op's replay
    get the kernel's jaxpr - and, within one module, its Mosaic lowering -
    without walking the body again (0.3-0.6 s a walk). ``interpreted``
    (``common.interpret()``, which the calls read as they are built) is
    part of the key only.

    A grid step owns one (batch, ``kh`` key heads, tile): the backward's
    index maps walk the tiles in reverse, and the key heads' value heads
    are neighbours in v's columns and in the head axis of the per-head
    arrays. ``channel``: the channel-gated rule's calls (``kda_chunk_*``):
    the gate and its cotangent are blocked as q and k are, in f32, and the
    kept states are ``[dv, dk]``."""
    heads = Hv // Hk * kh                           # value heads a step
    assert Sp % tile == 0 and tile % ROWS == 0, (Sp, tile)
    grid, groups = (B, Hk // kh, Sp // tile), tile // ROWS
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)    # noqa: E731
    qk3 = jax.ShapeDtypeStruct((B, Sp, Hk * dk), dtype)
    v3 = jax.ShapeDtypeStruct((B, Sp, Hv * dv), dtype)
    per_row = f32(B, Hv, Sp // ROWS, ROWS)
    state = (dv, dk) if channel else (dk, dv)
    kept = [f32(B, Hv, Sp // ROWS, n, d) for n, d in (state, (ROWS, ROWS))]
    gate3 = f32(B, Sp, Hk * dk) if channel else per_row

    def specs(where):
        cols = lambda d: pl.BlockSpec(                  # noqa: E731
            (1, tile, d), lambda b, h, t: (b, where(t), h))
        rows = pl.BlockSpec((1, heads, groups, ROWS),
                            lambda b, h, t: (b, h, where(t), 0))
        square = lambda n, d: pl.BlockSpec(             # noqa: E731
            (1, heads, groups, n, d),
            lambda b, h, t: (b, h, where(t), 0, 0))
        return (cols(kh * dk), cols(heads * dv), rows,
                [square(*state), square(ROWS, ROWS)])

    common = dict(grid=grid,
                  scratch_shapes=[pltpu.VMEM((heads,) + state, _F32)],
                  compiler_params=pltpu.CompilerParams(**_PARAMS))
    qk, vo, rows, squares = specs(lambda t: t)
    gate = qk if channel else rows
    fwd, fwd_keep = (named_pallas_call(
        "kda_chunk_fwd" if channel else "gdn_chunk_fwd",
        functools.partial(_fwd_kernel, C=C, groups=groups, eps=eps,
                          save=save, channel=channel),
        in_specs=[qk, qk, vo, gate, rows],
        out_specs=[vo] + (squares if save else []),
        out_shape=[v3] + (kept if save else []), **common)
        for save in (False, True))
    qk, vo, rows, squares = specs(lambda t: grid[2] - 1 - t)
    gate = qk if channel else rows
    bwd = named_pallas_call(
        "kda_chunk_bwd" if channel else "gdn_chunk_bwd",
        functools.partial(_bwd_kernel, C=C, groups=groups, eps=eps,
                          channel=channel),
        in_specs=[qk, qk, vo, gate, rows] + squares + [vo],
        out_specs=[qk, qk, vo, gate, rows],
        out_shape=[qk3, qk3, v3, gate3, per_row], **common)
    return {"fwd": fwd, "fwd_keep": fwd_keep, "bwd": bwd}


def _call(which, q3, v3, g, dk, dv, C, eps):
    """The call ``which`` of the inputs' signature; the gate's rank says
    which rule (``[B, Sp, H * dk]``: a channel)."""
    B, Sp = v3.shape[:2]
    Hk = q3.shape[2] // dk
    return _calls(B, Sp, Hk, v3.shape[2] // dv, v3.dtype, dk, dv, C, eps,
                  min(TILE, Sp), KEY_HEADS if Hk % KEY_HEADS == 0 else 1,
                  interpret(), g.ndim == 3)[which]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _core(q3, k3, v3, gc, beta, dk, dv, C, eps):
    return _call("fwd", q3, v3, gc, dk, dv, C, eps)(q3, k3, v3, gc, beta)[0]


def _core_fwd(q3, k3, v3, gc, beta, dk, dv, C, eps):
    # a recomputed segment keeps all three: its replay then has no use for
    # the forward kernel, and no forward call runs in it
    o3, states, inverses = (
        keep_across_recompute(x, "delta_rule") for x in
        _call("fwd_keep", q3, v3, gc, dk, dv, C, eps)(q3, k3, v3, gc, beta))
    return o3, (q3, k3, v3, gc, beta, states, inverses)


def _core_bwd(dk, dv, C, eps, res, do3):
    _count("kda_pallas_bwd" if res[3].ndim == 3 else "pallas_bwd")
    return tuple(_call("bwd", res[0], res[2], res[3], dk, dv, C, eps)(
        *res, do3))


_core.defvjp(_core_fwd, _core_bwd)


def _count(impl):
    from ..fluid.ops import linear_attention

    linear_attention._count(impl)


def gated_delta_rule_pallas(q, k, v, g, beta, chunk_size=64,
                            l2norm_eps=None):
    """The kernel path. q, k [B, S, Hk, dk], v [B, S, Hv, dv] with Hv a
    multiple of Hk, g (log decay, <= 0) and beta [B, S, Hv] in f32; or g
    [B, S, Hv, dk], a decay a key channel, with Hk = Hv: the channel-gated
    rule and its kernels. Returns o [B, S, Hv, dv] in v's dtype. q and k
    come normalised and
    scaled by the caller, or, with ``l2norm_eps``, raw: the kernels then
    normalise them over the head dim (``x * rsqrt(sum(x^2) + eps)``; q
    times ``dk ** -0.5``) on the rows they have in VMEM, forward and
    backward. The sequence is padded (beta = 0, g = 0: a padded position
    leaves the state alone) to whole groups, and past one tile to whole
    tiles."""
    B, S, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    C = int(chunk_size)
    assert supported(dk, dv, C) and Hv % Hk == 0, (q.shape, v.shape, C)
    channel = g.ndim == 4
    assert not channel or (Hk == Hv and g.shape[3] == dk), (q.shape, g.shape)
    _count("kda_pallas" if channel else "pallas")
    Sp = -(-S // ROWS) * ROWS
    if Sp > TILE:
        Sp = -(-S // TILE) * TILE
    pad = Sp - S
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                   for t in (g, beta))

    def head_rows(t):   # [B, Sp, Hv] -> [B, Hv, Sp / ROWS, ROWS]
        return jnp.moveaxis(t, 2, 1).reshape(B, Hv, Sp // ROWS, ROWS)

    if channel:     # as projected; the kernels make the running sum
        gate = g.astype(_F32).reshape(B, Sp, Hv * dk)
    else:
        gc = jnp.cumsum(g.astype(_F32).reshape(B, Sp // C, C, Hv), axis=2)
        gate = head_rows(gc.reshape(B, Sp, Hv))
    o3 = _core(q.astype(v.dtype).reshape(B, Sp, Hk * dk),
               k.astype(v.dtype).reshape(B, Sp, Hk * dk),
               v.reshape(B, Sp, Hv * dv), gate,
               head_rows(beta.astype(_F32)), dk, dv, C,
               None if l2norm_eps is None else float(l2norm_eps))
    return o3.reshape(B, Sp, Hv, dv)[:, :S]
