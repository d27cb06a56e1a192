"""Linear-attention ops: the causal depthwise convolution and the gated
delta rule of a Gated DeltaNet layer (Yang et al. 2024, "Gated Delta
Networks"; the token mixer of three in four Qwen3-Next layers).

Per head, with a ``[dk, dv]`` state ``S_0 = 0``::

    S' = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T;   o_t = S_t^T q_t

The lowering is the CHUNKED form (chunks of ``chunk_size`` positions):
inside a chunk the rank-one updates are folded into one unit-lower
triangular system, solved by block inversion; between chunks the state is
carried. Two implementations of the same equations and precisions: the
Pallas kernels of ``kernels/delta_rule.py`` (forward and backward, a
chunk's ``[C, C]`` work in VMEM) where head dims are multiples of 128, the
chunk packs into a 128-row group and a TPU (or the interpreter) is there;
else ``gated_delta_rule_chunked`` in plain XLA - a ``lax.scan`` over the
chunks holds the two matmuls that touch the state, everything else is
batched over all chunks - which is also the kernels' oracle. The
``autodiff`` op differentiates either like any lowering.

The same op runs the CHANNEL-GATED rule (Kimi Delta Attention; Kimi
Linear's token mixer three layers in four), told by its gate's rank: ``A``
[B, S, Hv, dk] with ``DtBias`` [Hv * dk] is a log decay a key channel,
``S' = Diag(exp(g_t)) S_{t-1}`` with the rest as above. Its chunked form
(``kda_chunked`` here, the kernels ``kda_chunk_fwd`` / ``kda_chunk_bwd``
beside the scalar rule's) has no ``[C, C]`` decay matrix: the decay sits
inside every contraction over dk, and is formed pair by pair in levels so
that no factor exceeds 1 (``kernels/delta_rule.py``'s header has the
equations). Dispatch is the same: by what the shapes show.
"""

import functools

from ..registry import register

_BASE = 16      # the diagonal blocks inverted by forward substitution


def _count(impl, name="gdn_dispatch_total", op="gated_delta_rule"):
    """Trace-time record of which implementation a dispatch took (one per
    traced site, not per step), beside ``attn_kernel_dispatch_total``."""
    from .. import monitor

    monitor.counter(
        name, op + " lowerings traced, by implementation (trace-time: "
        "one per traced program, not per step)", labels={"impl": impl}).inc()


def _count_conv(impl):
    """The convolution's: ``pallas``, ``pallas_bwd`` or ``xla``."""
    _count(impl, "conv_dispatch_total", "causal_conv1d")


def _taps(xp, w, S):
    """``sum_j w[:, j] * xp[:, j:j + S]``: K shifted multiply-adds over one
    padded array, in f32, which XLA fuses into one pass."""
    import jax.numpy as jnp

    xp, w = xp.astype(jnp.float32), w.astype(jnp.float32)
    return sum(xp[:, j:j + S, :] * w[:, j] for j in range(w.shape[1]))


@functools.lru_cache(maxsize=None)
def _causal_conv(silu=False):
    """``act(conv(x [B, S, C], w [C, K]))`` in plain XLA with a backward
    written out: the input gradient is the same K taps run the other way
    over the padded cotangent, so what stays live is x and w, not K f32
    copies of x. With ``silu`` the activation ``z * sigmoid(z)`` is applied
    to the f32 sum before the one rounding, and the backward makes z again
    from x. The kernels' oracle (``kernels/causal_conv.py`` has the
    equations)."""
    import jax
    import jax.numpy as jnp

    def padded(x, K):       # K - 1 zero rows before the start, in f32
        return jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)

    @jax.custom_vjp
    def conv(x, w):
        z = _taps(padded(x, w.shape[1]), w, x.shape[1])
        return (z * jax.nn.sigmoid(z) if silu else z).astype(x.dtype)

    def bwd(res, dy):
        x, w = res
        K, S = w.shape[1], x.shape[1]
        xp, dz = padded(x, K), dy.astype(jnp.float32)
        if silu:
            z = _taps(xp, w, S)
            s = jax.nn.sigmoid(z)
            dz = dz * (s * (1.0 + z * (1.0 - s)))
        dx = _taps(jnp.pad(dz, ((0, 0), (0, K - 1), (0, 0))), w[:, ::-1], S)
        dw = jnp.stack([jnp.sum(dz * xp[:, j:j + S, :], axis=(0, 1))
                        for j in range(K)], axis=1)
        return dx.astype(x.dtype), dw.astype(w.dtype)

    conv.defvjp(lambda x, w: (conv(x, w), (x, w)), bwd)
    return conv


def causal_conv(x, w, activation=""):
    """``act(conv(x, w))`` by the Pallas kernels where the input allows
    them (``kernels/causal_conv.py:supported``: shapes, dtype, a TPU or
    the interpreter), else by the XLA form of the same equations."""
    from ...kernels import causal_conv as kernels

    if activation not in ("", "swish"):
        raise ValueError("causal_conv1d: activation %r is neither '' nor "
                         "'swish'" % (activation,))
    silu = activation == "swish"
    if kernels.supported(x.shape, w.shape, x.dtype):
        _count_conv("pallas")
        return kernels.causal_conv_pallas(x, w, silu)
    _count_conv("xla")
    return _causal_conv(silu)(x, w)


@register("causal_conv1d")
def _causal_conv1d(ctx, op):
    """Depthwise causal convolution along the sequence: X [B, S, C],
    Filter [C, K], ``Out[t] = act(sum_j Filter[:, j] * X[t - (K-1) + j])``
    (zeros before the start), no bias; ``activation`` is ``""`` or
    ``"swish"`` (``z * sigmoid(z)`` on the f32 sum, rounded once). K
    shifted multiply-adds in f32, as one Pallas kernel forward and one
    backward or in XLA: for K = 4 that is cheaper on the chip than a
    grouped convolution."""
    ctx.set_output(op, "Out", causal_conv(
        ctx.get_input(op, "X"), ctx.get_input(op, "Filter"),
        op.attrs.get("activation", "")))


def _inv_blocks(m):
    """Inverse of unit lower triangular ``m`` [..., n, n], n <= _BASE, by
    forward substitution, one row at a time (row i of the inverse is
    ``e_i - m[i, :i] @ rows[:i]``)."""
    import jax.numpy as jnp

    n = m.shape[-1]
    eye = jnp.eye(n, dtype=m.dtype)
    rows = [jnp.broadcast_to(eye[0], m.shape[:-2] + (n,))]
    for i in range(1, n):
        prev = jnp.stack(rows, axis=-2)                 # [..., i, n]
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", m[..., i, :i], prev, precision="highest"))
    return jnp.stack(rows, axis=-2)


def _inv_recursive(m):
    """Block inversion: ``inv([[a, 0], [c, d]]) = [[ia, 0], [-id c ia,
    id]]``, the two halves inverted in one batched call."""
    import jax.numpy as jnp

    n = m.shape[-1]
    if n <= _BASE:
        return _inv_blocks(m)
    h = n // 2
    both = _inv_recursive(jnp.stack([m[..., :h, :h], m[..., h:, h:]]))
    ia, idd = both[0], both[1]
    low = -jnp.einsum("...ij,...jk,...kl->...il", idd, m[..., h:, :h], ia,
                      precision="highest")
    top = jnp.concatenate([ia, jnp.zeros_like(ia)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([low, idd], axis=-1)],
                           axis=-2)


@functools.lru_cache(maxsize=None)
def _inv_unit_lower():
    """``inv(m)`` for unit lower triangular ``m`` [..., n, n] (n a power
    of two times at most ``_BASE``), with the two-matmul backward."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def inv(m):
        return _inv_recursive(m)

    def fwd(m):
        t = _inv_recursive(m)
        return t, t

    def bwd(t, dt):
        # d(inv) = -inv dM inv; only the strict lower part of M varies
        dm = -jnp.einsum("...ji,...jk,...lk->...il", t, dt, t,
                         precision="highest")
        return (jnp.tril(dm, -1),)

    inv.defvjp(fwd, bwd)
    return inv


def gated_delta_rule_chunked(q, k, v, g, beta, chunk_size=64):
    """The chunked gated delta rule. q, k [B, S, H, dk] (normalised and
    scaled by the caller), v [B, S, H, dv], g (log decay, <= 0) and beta
    [B, S, H] in f32. Returns o [B, S, H, dv] in f32. Matmul operands go
    in v's dtype (bf16 under AMP) with f32 accumulation; decays, the
    triangular system and the state stay f32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    cd = v.dtype
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = int(chunk_size)
    pad = (-S) % C
    if pad:     # beta = 0, g = 0: a padded position leaves the state alone
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (g, beta))
    N = (S + pad) // C

    def chunks(t):      # [B, S, H, ...] -> [B, H, N, C, ...]
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape((B, H, N, C) + t.shape[3:])

    q, k, v, g, beta = (chunks(t) for t in (q, k, v, g, beta))
    gc = jnp.cumsum(g.astype(f32), axis=-1)             # [B, H, N, C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    # decay from position j to position i of a chunk, i >= j
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kf = k.astype(f32)
    k_beta = kf * beta[..., None]
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)
    a = mm("bhnid,bhnjd->bhnij", k_beta.astype(cd), k) * decay
    t = _inv_unit_lower()(jnp.eye(C, dtype=f32) + jnp.tril(a, -1))
    rhs = jnp.concatenate([v.astype(f32) * beta[..., None],
                           k_beta * jnp.exp(gc)[..., None]], axis=-1)
    uw = mm("bhnij,bhnjd->bhnid", t.astype(cd), rhs.astype(cd))
    u, w = uw[..., :dv], uw[..., dv:]
    g_last = gc[..., -1]                                # [B, H, N]
    k_dec = (kf * jnp.exp(g_last[..., None] - gc)[..., None]).astype(cd)

    def step(state, xs):
        u_n, w_n, k_n, gl_n = xs
        v_new = u_n - mm("bhcd,bhde->bhce", w_n, state.astype(cd))
        new = state * jnp.exp(gl_n)[..., None, None] + mm(
            "bhcd,bhce->bhde", k_n, v_new.astype(cd))
        return new, (state.astype(cd), v_new.astype(cd))

    lead = lambda x: jnp.moveaxis(x, 2, 0)              # noqa: E731
    _, (states, v_new) = jax.lax.scan(
        step, jnp.zeros((B, H, dk, dv), f32),
        (lead(u), lead(w.astype(cd)), lead(k_dec), lead(g_last)))
    states, v_new = jnp.moveaxis(states, 0, 2), jnp.moveaxis(v_new, 0, 2)
    qk = mm("bhnid,bhnjd->bhnij", q, k) * decay
    o = mm("bhncd,bhnde->bhnce",
           (q.astype(f32) * jnp.exp(gc)[..., None]).astype(cd), states) \
        + mm("bhnij,bhnjd->bhnid", qk.astype(cd), v_new)
    o = jnp.moveaxis(o.reshape(B, H, N * C, dv), 1, 2)
    return o[:, :S]


def kda_chunked(q, k, v, g, beta, chunk_size=64):
    """The chunked CHANNEL-gated delta rule. q, k [B, S, H, dk]
    (normalised and scaled by the caller), v [B, S, H, dv], g [B, S, H, dk]
    (log decay a key channel, <= 0) and beta [B, S, H] in f32. Returns o
    [B, S, H, dv] in f32. Precisions as ``gated_delta_rule_chunked``. A
    pairwise decay ``exp(Gc_i - Gc_j)`` is the product of two factors <= 1
    through the row r where i's and j's positions first part (level b = 1,
    2, .. C/2: i in an odd block of b, j in the even block before it, r
    the odd block's first row); ``exp(-Gc)`` is never formed."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    cd = v.dtype
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = int(chunk_size)
    assert C & (C - 1) == 0, "the chunk is a power of two: %d" % C
    pad = (-S) % C
    if pad:     # beta = 0, g = 0: a padded position leaves the state alone
        q, k, v, g = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    N = (S + pad) // C

    def chunks(t):      # [B, S, H, ...] -> [B, H, N, C, ...]
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape((B, H, N, C) + t.shape[3:])

    q, k, v, g, beta = (chunks(t) for t in (q, k, v, g, beta))
    gc = jnp.cumsum(g.astype(f32), axis=3)              # [B, H, N, C, dk]
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)
    qf, kf = q.astype(f32), k.astype(f32)
    k_beta = kf * beta[..., None]
    idx = jnp.arange(C)
    a = jnp.zeros((B, H, N, C, C), f32)
    # the diagonal of q k^T carries no decay
    qk = jnp.eye(C, dtype=f32) * jnp.sum(qf * kf, -1)[..., None]
    b = 1
    while b < C:
        odd = (idx // b) % 2 == 1
        d = gc - gc[..., idx // (2 * b) * (2 * b) + b, :]
        e = jnp.exp(jnp.where(odd[:, None], d, -d))     # both sides <= 1
        pair = (idx[:, None] // (2 * b) == idx[None, :] // (2 * b)) \
            & odd[:, None] & ~odd[None, :]
        ke = (kf * e).astype(cd)
        a = a + jnp.where(pair, mm("bhnid,bhnjd->bhnij",
                                   (k_beta * e).astype(cd), ke), 0.0)
        qk = qk + jnp.where(pair, mm("bhnid,bhnjd->bhnij",
                                     (qf * e).astype(cd), ke), 0.0)
        b *= 2
    t = _inv_unit_lower()(jnp.eye(C, dtype=f32) + a)
    e_g = jnp.exp(gc)
    rhs = jnp.concatenate([v.astype(f32) * beta[..., None], k_beta * e_g],
                          axis=-1)
    uw = mm("bhnij,bhnjd->bhnid", t.astype(cd), rhs.astype(cd))
    u, w = uw[..., :dv], uw[..., dv:]
    g_last = gc[..., -1, :]                             # [B, H, N, dk]
    k_dec = (kf * jnp.exp(g_last[..., None, :] - gc)).astype(cd)

    def step(state, xs):
        u_n, w_n, k_n, gl_n = xs
        v_new = u_n - mm("bhcd,bhde->bhce", w_n, state.astype(cd))
        new = state * jnp.exp(gl_n)[..., None] + mm(
            "bhcd,bhce->bhde", k_n, v_new.astype(cd))
        return new, (state.astype(cd), v_new.astype(cd))

    lead = lambda x: jnp.moveaxis(x, 2, 0)              # noqa: E731
    _, (states, v_new) = jax.lax.scan(
        step, jnp.zeros((B, H, dk, dv), f32),
        (lead(u), lead(w.astype(cd)), lead(k_dec), lead(g_last)))
    states, v_new = jnp.moveaxis(states, 0, 2), jnp.moveaxis(v_new, 0, 2)
    o = mm("bhncd,bhnde->bhnce", (qf * e_g).astype(cd), states) \
        + mm("bhnij,bhnjd->bhnid", qk.astype(cd), v_new)
    o = jnp.moveaxis(o.reshape(B, H, N * C, dv), 1, 2)
    return o[:, :S]


@register("gated_delta_rule")
def _gated_delta_rule(ctx, op):
    """Q, K [B, S, Hk, dk], V [B, S, Hv, dv] (Hv a multiple of Hk: each
    key head serves Hv/Hk value heads), A and B [B, S, Hv] (the decay's
    and beta's pre-activations), ALog, DtBias [Hv] -> Out [B, S, Hv, dv].
    ``g = -exp(ALog) * softplus(A + DtBias)`` and ``beta = sigmoid(B)``
    in f32; q and k are L2-normalised over the head dim and q is scaled
    by ``dk ** -0.5``. A [B, S, Hv, dk] with DtBias [Hv * dk] (ALog stays
    [Hv]; Hk = Hv) is a decay a key CHANNEL: the channel-gated rule."""
    import jax
    import jax.numpy as jnp

    from ...kernels import delta_rule

    f32 = jnp.float32
    q, k, v = (ctx.get_input(op, s) for s in ("Q", "K", "V"))
    a = ctx.get_input(op, "A").astype(f32)
    b = ctx.get_input(op, "B").astype(f32)
    a_log = ctx.get_input(op, "ALog").astype(f32)
    dt_bias = ctx.get_input(op, "DtBias").astype(f32)
    channel = a.ndim == 4
    if channel:
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            a + dt_bias.reshape(a.shape[2:]))
    else:
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    beta = jax.nn.sigmoid(b)
    rep = v.shape[2] // q.shape[2]
    assert rep * q.shape[2] == v.shape[2], (q.shape, v.shape)
    chunk = int(op.attr("chunk_size", 64))
    eps = 1e-6          # the family's l2norm: x * rsqrt(sum(x^2) + eps)
    # by what the input shows: the kernels where the shapes fill their
    # tiles (and a TPU or the interpreter is there), the XLA form elsewhere
    if delta_rule.supported(q.shape[-1], v.shape[-1], chunk):
        out = delta_rule.gated_delta_rule_pallas(       # counts "pallas"
            q, k, v, g, beta, chunk_size=chunk, l2norm_eps=eps)
    else:
        _count("kda_chunked" if channel else "chunked")
        qf, kf = q.astype(f32), k.astype(f32)
        qf = qf * jax.lax.rsqrt(jnp.sum(qf * qf, -1, keepdims=True) + eps)
        kf = kf * jax.lax.rsqrt(jnp.sum(kf * kf, -1, keepdims=True) + eps)
        qn, kn = (qf * q.shape[-1] ** -0.5).astype(v.dtype), \
            kf.astype(v.dtype)
        if rep > 1:
            qn, kn = (jnp.repeat(t, rep, axis=2) for t in (qn, kn))
        out = (kda_chunked if channel else gated_delta_rule_chunked)(
            qn, kn, v, g, beta, chunk_size=chunk)
    ctx.set_output(op, "Out", out.astype(v.dtype))
