"""Family ``kimi_linear_train``: next-token training of a Kimi Linear block
stack (Kimi Delta Attention x3 : NoPE latent attention x1; a dense MLP in
the leading layer, a sigmoid-routed expert layer with a shared expert in
the others) through ``models.kimi_linear.build_train_program`` and
``fluid.Executor.run``, one expert-parallel rank's share.

The program's side (``build``) is the system under test; the rest is the
yardstick: weights and feeds from the seed, FLOPs from shapes, and the
plain float32 reference of the same step, which imports nothing of the
program. The reference's leaves carry the program's parameter names.

The share: the router scores all ``num_experts_total`` experts and keeps
``num_experts_per_token``; experts ``expert_offset`` .. ``+ num_experts``
are held here and what the absent ones would add is left out, in the
program and in the reference alike; the shared expert is whole; ids and the
loss are over the ``vocab_size`` rows held here. The router's selection-only
bias gets no gradient and is no leaf: it is zero on both sides unless a
feed carries ``router_bias`` [layers, num_experts_total] (the tests do; the
program's ``Step`` then sets its frozen variables from it).
"""

import copy
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import compare
import fluid_step

# planted faults of this model's own, for ``reference_loss(fault=...)``
FAULTS = ("scalar_gate", "softmax_router", "no_experts")
RECURRENCE_BLOCK = 64       # recurrence steps recomputed together
KDA_HEAD_GROUP = 4          # KDA heads whose activations are live together
MLA_HEAD_GROUP = 8          # MLA heads whose activations are live together
MLP_BLOCK = 4096            # positions of a dense MLP live together
L2NORM_EPS = 1e-6           # the delta-rule family's: x * rsqrt(sum x^2 + eps)


# -- sizes -------------------------------------------------------------------
def tiny(cfg, mix):
    """The CPU rehearsal's preset: toy widths, the mix's ``rehearse``
    shapes, all five layer kinds kept. Proves nothing about the chip."""
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    mix.update(mix["rehearse"])
    cfg.update(vocab_size=512, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
               num_experts_total=16, num_experts_per_token=3,
               moe_intermediate_size=32)
    cfg["linear_attn_config"].update(num_heads=2, head_dim=16)
    return cfg, mix


def tokens_per_step(cfg, mix):
    return mix["batch"] * mix["seq_len"]


def _is_full(cfg, i):
    """Layer ``i`` counted from 0 (the config's lists count from 1)."""
    return i + 1 in cfg["linear_attn_config"]["full_attn_layers"]


def _is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def _dims(cfg):
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def _causal_pairs(s):
    return s * (s + 1) // 2


def _mla_layers(cfg):
    return sum(_is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))


def flops(cfg, mix):
    """Matmul FLOPs of one training step from shapes, by the qwen family's
    rules: backward = 2 x forward, 2*M*N*K a matmul, nothing recomputed is
    counted, gathers and elementwise work not counted. Per token forward:
    every projection (the low-rank gates too), the dense MLP, the shared
    expert, the routed experts at the EXPECTED ``num_experts_per_token *
    num_experts / num_experts_total`` a token (even routing), the router
    and the head; the delta rule at the RECURRENCE's own count, ``3 * 2 *
    dk * dv`` a head and position, not the chunked form's, which does more.
    Causal attention: its ``S (S + 1) / 2`` pairs a head and row at ``2 *
    (dqk + dv)``."""
    s = mix["seq_len"]
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    Hl, d, _, H, dn, ds, dv, r = _dims(cfg)
    f, I = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    kda = 2 * h * 3 * Hl * d + 2 * 2 * (h * d + d * Hl * d) + 2 * h * Hl \
        + 2 * Hl * d * h + 6 * d * d * Hl
    mla = 2 * h * H * (dn + ds) + 2 * h * (r + ds) + 2 * r * H * (dn + dv) \
        + 2 * H * dv * h
    share = cfg["num_experts_per_token"] * cfg["num_experts"] \
        / cfg["num_experts_total"]
    moe = 2 * h * cfg["num_experts_total"] + share * 6 * h * f \
        + cfg["num_shared_experts"] * 6 * h * f
    L = cfg["num_hidden_layers"]
    n_full = _mla_layers(cfg)
    n_dense = sum(_is_dense(cfg, i) for i in range(L))
    per_token = (L - n_full) * kda + n_full * mla + n_dense * 6 * h * I \
        + (L - n_dense) * moe + 2 * h * V
    pairs = n_full * mix["batch"] * H * _causal_pairs(s) * 2 * (dn + ds + dv)
    return 3 * (per_token * tokens_per_step(cfg, mix) + pairs)


def attention_cost(cfg, mix):
    """``(flops, bytes)`` one step's attention kernels need, the latent
    attention layers, forward and backward, at the two widths (d = nope +
    rope for q and k, dv for v): the causal pairs' matmul FLOPs - forward
    ``2 (d + dv)`` a pair, dq's pass ``2 (d + dv + d)`` (scores, dP, dq),
    dk/dv's ``2 (d + dv + dv + d)`` (scores, dV, dP, dk) - with neither the
    masked halves of diagonal tiles nor skipped tiles nor recomputation
    counted; q, k, v, o, do, dq, dk, dv once each in the 2-byte type."""
    b, s = mix["batch"], mix["seq_len"]
    _, _, _, H, dn, ds, dv, _ = _dims(cfg)
    d = dn + ds
    n = _mla_layers(cfg)
    a_pair = 2 * (d + dv) + 2 * (2 * d + dv) + 2 * (2 * d + 2 * dv)
    return (n * b * H * _causal_pairs(s) * a_pair,
            n * b * H * s * (4 * d + 4 * dv) * 2)


def gdn_cost(cfg, mix):
    """``(flops, bytes)`` one step's delta-rule cores need, all KDA layers,
    forward and backward (backward = 2 x forward): the matmuls of the
    chunked channel-gated form at chunk C a chunk and head - the five
    products inside the chunk (k k^T and q k^T at 2*C*C*dk each ONCE,
    however many levels the pairwise decay takes; the triangular system
    applied to [v | k], 2*C*C*(dv + dk); the local output, 2*C*C*dv) and the
    three with the state (2*C*dk*dv each); the inversion and the pairwise
    decays are not counted. Bytes: q, k, v, o in the 2-byte type, g in f32
    a CHANNEL and beta in f32, read or written once, and their
    cotangents."""
    b, s = mix["batch"], mix["seq_len"]
    H, d = _dims(cfg)[:2]
    C = cfg.get("kda_chunk_size", 64)
    n = cfg["num_hidden_layers"] - _mla_layers(cfg)
    a_chunk = 2 * C * (2 * C * d + C * (d + d) + C * d + 3 * d * d)
    chunks = b * H * -(-s // C)
    nbytes = b * s * (4 * H * d * 2 + H * d * 4 + H * 4)
    return (n * 3 * a_chunk * chunks, n * 2 * nbytes)


# -- weights and feeds from the seed -----------------------------------------
def param_shapes(cfg):
    """The trainable leaves."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    Hl, d, K, H, dn, ds, dv, r = _dims(cfg)
    E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs, I = f * cfg["num_shared_experts"], cfg["intermediate_size"]
    shapes = {"embed_tokens": (V, h), "lm_head_w": (h, V),
              "final_norm": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer_%d_" % i
        shapes.update({p + "norm1": (h,), p + "norm2": (h,)})
        if _is_full(cfg, i):
            shapes.update({
                p + "mla_q_w": (h, H * (dn + ds)),
                p + "mla_kv_a_w": (h, r + ds), p + "mla_kv_norm": (r,),
                p + "mla_kv_b_w": (r, H * (dn + dv)),
                p + "mla_o_w": (H * dv, h)})
        else:
            shapes.update({
                p + "kda_qkv_w": (h, 3 * Hl * d),
                p + "kda_conv_w": (3 * Hl * d, K),
                p + "kda_f_a_w": (h, d), p + "kda_f_b_w": (d, Hl * d),
                p + "kda_b_w": (h, Hl), p + "kda_a_log": (Hl,),
                p + "kda_dt_bias": (Hl * d,),
                p + "kda_g_a_w": (h, d), p + "kda_g_b_w": (d, Hl * d),
                p + "kda_norm": (d,), p + "kda_o_w": (Hl * d, h)})
        if _is_dense(cfg, i):
            shapes.update({p + "mlp_gate_w": (h, I), p + "mlp_up_w": (h, I),
                           p + "mlp_down_w": (I, h)})
        else:
            shapes.update({
                p + "moe_router_w": (h, cfg["num_experts_total"]),
                p + "moe_gate_w": (E, h, f), p + "moe_up_w": (E, h, f),
                p + "moe_down_w": (E, f, h),
                p + "moe_shared_gate_w": (h, fs),
                p + "moe_shared_up_w": (h, fs),
                p + "moe_shared_down_w": (fs, h)})
    return shapes


def init_params(cfg, seed):
    """Every leaf in one jitted call on the device, float32 as the program
    keeps its master weights: N(0, initializer_range); a norm's weight is
    1 + that; ``A_log`` = log U(1, 16) a head and ``dt_bias`` the
    inverse softplus of a time step log-uniform in [0.001, 0.1] a channel
    (the layer's own initialiser), so g spans -1.6 to -0.001 a position."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.endswith("kda_a_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif name.endswith("kda_dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                x = jax.random.normal(k, shape, jnp.float32)
                out[name] = (
                    1.0 + std * x if name.endswith(
                        ("norm1", "norm2", "final_norm", "_norm"))
                    else std * x)
        return out

    return make(compare.seed_key(seed))


def feeds(cfg, mix, seed, n):
    """``n`` batches as numpy: ids uniform over the vocabulary slice held
    here, every row full length; the label of a position is the next id.
    No position input: no layer takes one."""
    rng = np.random.default_rng([int(seed), 1])
    b, s, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
    out = []
    for _ in range(n):
        ids = rng.integers(0, V, (b, s + 1)).astype("int64")
        out.append({"tokens": ids[:, :-1].copy(), "labels": ids[:, 1:].copy()})
    return out


def half_batch(feed):
    """The planted fault "half of the batch left out". The cell's batch is
    ONE row, so it is the second half of the row's positions that is left
    out of the loss: the first half's labels stand in their place, and the
    mean is over the first half twice. (``compare.half_batch`` halves the
    rows, and one row has no half.)"""
    if feed["tokens"].shape[0] > 1:
        return compare.half_batch(feed, rows_of="tokens")
    out = {k: np.array(v) for k, v in feed.items()}
    half = out["labels"].shape[1] // 2
    out["tokens"][:, half:2 * half] = out["tokens"][:, :half]
    out["labels"][:, half:2 * half] = out["labels"][:, :half]
    return out


# -- the plain reference -----------------------------------------------------
def _largest_divisor(n, most):
    return max(d for d in range(1, most + 1) if n % d == 0)


def _take(m, i, width, axis):
    """Group ``i``'s ``width`` columns (or rows) of a leaf as it lies."""
    return jax.lax.dynamic_slice_in_dim(m, i * width, width, axis)


def _heads_a_group(heads, most):
    """At most ``most`` heads a group and, so that a toy size has groups
    too, at most half the heads."""
    return _largest_divisor(heads, max(1, min(most, heads // 2)))


def reference_loss(cfg, mm, fault=None):
    """``loss(params, feed)`` of the step as the model's ``config.json`` and
    the configuration file's ``assumed`` give it, float32, every matmul
    through ``mm``. Departures for memory at the timed size, none changing
    a number: rows are mapped one at a time; every layer, inside it its
    mixer and its feed-forward part, every group of ``KDA_HEAD_GROUP`` KDA heads and of
    ``MLA_HEAD_GROUP`` MLA heads (a head's columns of every projection, its
    filters and its rows of the output projection: heads never mix before
    it), every block of
    ``RECURRENCE_BLOCK`` recurrence steps, every block of queries, every
    block of ``MLP_BLOCK`` positions of an MLP, every expert and every
    block of the head's positions is recomputed on the way back
    (``jax.checkpoint``): beside 9.6e9 B of parameters, moments and
    gradient a whole layer's float32 activations at 16,384 positions do
    not fit. KDA is the RECURRENCE, position by
    position (the program's chunked algebra is what is under test). MLA
    concatenates the 192-wide key. The expert layer is a dense loop over
    the experts held here, each applied to every token under its weight's
    column. ``fault``: one of ``FAULTS``, planted - ``scalar_gate`` (g
    replaced by its mean over a head's channels: the scalar rule),
    ``softmax_router`` (softmax scores, no scaling factor), ``no_experts``
    (the routed sum left out)."""
    assert fault in (None,) + FAULTS, fault
    eps = cfg["rms_norm_eps"]
    Hl, d, K, H, dn, ds, dv, r = _dims(cfg)
    off, E = cfg.get("expert_offset", 0), cfg["num_experts"]
    top_k, factor = cfg["num_experts_per_token"], cfg["routed_scaling_factor"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                 + L2NORM_EPS)

    def recurrence(q, k, v, g, beta):
        """q, k, g [S, H, dk], v [S, H, dv], beta [S, H] -> [S, H, dv]:
        S' = Diag(exp(g_t)) S; d = beta_t (v_t - S'^T k_t); S = S' + k_t
        d^T; o_t = S^T q_t."""
        S = q.shape[0]
        pad = (-S) % RECURRENCE_BLOCK   # beta = 0, g = 0: state untouched
        xs = [jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
              for t in (q, k, v, g, beta)]
        xs = [t.reshape((-1, RECURRENCE_BLOCK) + t.shape[1:]) for t in xs]

        def step(state, x):
            q_t, k_t, v_t, g_t, b_t = x
            decayed = state * jnp.exp(g_t)[:, :, None]
            delta = b_t[:, None] * (v_t - mm("hkv,hk->hv", decayed, k_t))
            state = decayed + mm("hk,hv->hkv", k_t, delta)
            return state, mm("hkv,hk->hv", state, q_t)

        @jax.checkpoint
        def block(state, x):
            return jax.lax.scan(step, state, x, unroll=8)

        heads = q.shape[1]
        _, o = jax.lax.scan(block, jnp.zeros((heads, d, d), jnp.float32),
                            tuple(xs))
        return o.reshape((-1, heads, d))[:S]

    def low_rank(x, p, name):
        return mm("sr,rk->sk", mm("sh,hr->sr", x, p[name + "_a_w"]),
                  p[name + "_b_w"])

    def kda(x, p):
        """A group of heads at a time: [G, ...] leading every operand of
        the group function, which returns the group's part of Wo o."""
        S = x.shape[0]
        per = _heads_a_group(Hl, KDA_HEAD_GROUP)
        G, w = Hl // per, per * d                       # groups, columns
        fa = mm("sh,hr->sr", x, p["kda_f_a_w"])         # the gates' first
        ga = mm("sh,hr->sr", x, p["kda_g_a_w"])         # factors: shared

        @jax.checkpoint
        def group(i):
            # sliced here, from the leaves as they lie: a transposed copy
            # of every layer's weights would be made ahead of the loop
            w_qkv = _take(p["kda_qkv_w"].reshape(-1, 3, Hl * d), i, w, 2)
            w_conv = _take(p["kda_conv_w"].reshape(3, Hl * d, K), i, w, 1)
            w_fb, w_gb = (_take(p[n], i, w, 1)
                          for n in ("kda_f_b_w", "kda_g_b_w"))
            dt_bias, w_o = (_take(p[n], i, w, 0)
                            for n in ("kda_dt_bias", "kda_o_w"))
            a_log = _take(p["kda_a_log"], i, per, 0)
            w_b = _take(p["kda_b_w"], i, per, 1)
            qkv = mm("sh,hpk->spk", x, w_qkv)           # [S, 3, w]
            # causal depthwise convolution, K taps, no bias, then SiLU
            padded = jnp.pad(qkv, ((K - 1, 0), (0, 0), (0, 0)))
            qkv = jax.nn.silu(sum(padded[j:j + S] * w_conv[..., j]
                                  for j in range(K)))
            q, k, v = (qkv[:, j].reshape(S, per, d) for j in range(3))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (mm("sr,rk->sk", fa, w_fb) + dt_bias).reshape(S, per, d))
            if fault == "scalar_gate":
                g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
            beta = jax.nn.sigmoid(mm("sh,hk->sk", x, w_b))
            o = recurrence(l2norm(q) * d ** -0.5, l2norm(k), v, g, beta)
            o = rms(o, p["kda_norm"]) * jax.nn.sigmoid(
                mm("sr,rk->sk", ga, w_gb).reshape(S, per, d))
            return mm("sk,kh->sh", o.reshape(S, w), w_o)

        # (the sum is carried outside the recomputed part, as the experts')
        out, _ = jax.lax.scan(lambda acc, i: (acc + group(i), None),
                              jnp.zeros_like(x), jnp.arange(G))
        return out

    def mla(x, p):
        """A group of heads at a time, as ``kda``; the latent and the
        shared key part are made once."""
        S = x.shape[0]
        per = _heads_a_group(H, MLA_HEAD_GROUP)
        G = H // per
        kva = mm("sh,hk->sk", x, p["mla_kv_a_w"])       # [c | k_s]
        c, k_s = rms(kva[:, :r], p["mla_kv_norm"]), kva[:, r:]
        Qb = _largest_divisor(S, 128)
        cols = jnp.arange(S)

        @jax.checkpoint
        def group(i):
            w_q = _take(p["mla_q_w"], i, per * (dn + ds), 1)
            w_kvb = _take(p["mla_kv_b_w"], i, per * (dn + dv), 1)
            w_o = _take(p["mla_o_w"], i, per * dv, 0)
            q = mm("sh,hk->sk", x, w_q).reshape(S, per, dn + ds)
            kv = mm("sr,rk->sk", c, w_kvb).reshape(S, per, dn + dv)
            # a head's key: its own part, then the part all heads share;
            # no rotation on either (mla_use_nope)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                k_s[:, None], (S, per, ds))], -1)
            v = kv[..., dn:]

            @jax.checkpoint
            def q_block(args):
                qb, row0 = args                         # [Qb, per, dn + ds]
                sc = mm("qhd,shd->hqs", qb, k) * (dn + ds) ** -0.5
                rows = row0 + jnp.arange(Qb)
                sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
                return mm("hqs,shd->qhd", jax.nn.softmax(sc, -1), v)

            ctx = jax.lax.map(q_block, (
                q.reshape(S // Qb, Qb, per, dn + ds), jnp.arange(0, S, Qb)))
            return mm("sk,kh->sh", ctx.reshape(S, per * dv), w_o)

        out, _ = jax.lax.scan(lambda acc, i: (acc + group(i), None),
                              jnp.zeros_like(x), jnp.arange(G))
        return out

    def mlp(x, wg, wu, wd):
        return mm("sf,fh->sh", jax.nn.silu(mm("sh,hf->sf", x, wg))
                  * mm("sh,hf->sf", x, wu), wd)

    def mlp_in_blocks(x, wg, wu, wd):
        Pb = _largest_divisor(x.shape[0], MLP_BLOCK)
        return jax.lax.map(jax.checkpoint(lambda xb: mlp(xb, wg, wu, wd)),
                           x.reshape(-1, Pb, x.shape[1])).reshape(x.shape)

    def moe(x, p, bias):
        logits = mm("sh,he->se", x, p["moe_router_w"])
        if fault == "softmax_router":
            score, scale = jax.nn.softmax(logits, -1), 1.0
        else:
            score, scale = jax.nn.sigmoid(logits), factor
        _, ids = jax.lax.top_k(score + bias, top_k)     # bias: choice only
        chosen = jnp.sum(jax.nn.one_hot(ids, score.shape[-1]), 1)   # 0/1
        w = score * chosen / (jnp.sum(score * chosen, -1, keepdims=True)
                              + 1e-20) * scale
        held = w[:, off:off + E]                        # [S, E]

        @jax.checkpoint
        def one(wg, wu, wd, col):
            return col[:, None] * mlp(x, wg, wu, wd)

        # (the sum is carried outside the recomputed part: a carry inside
        # it would be kept once an expert)
        routed, _ = jax.lax.scan(
            lambda acc, e: (acc + one(*e), None), jnp.zeros_like(x),
            (p["moe_gate_w"], p["moe_up_w"], p["moe_down_w"], held.T))
        if fault == "no_experts":
            routed = jnp.zeros_like(x)
        return routed + mlp(x, p["moe_shared_gate_w"], p["moe_shared_up_w"],
                            p["moe_shared_down_w"])

    def layer(i, x, p, bias):
        mixer = mla if _is_full(cfg, i) else kda
        u = x + jax.checkpoint(mixer)(rms(x, p["norm1"]), p)
        h = rms(u, p["norm2"])
        if _is_dense(cfg, i):
            return u + mlp_in_blocks(h, p["mlp_gate_w"], p["mlp_up_w"],
                                     p["mlp_down_w"])
        return u + jax.checkpoint(moe)(h, p, bias)

    def row_loss(params, tokens, labels, router_bias):
        x = params["embed_tokens"][tokens]
        for i in range(cfg["num_hidden_layers"]):
            pre = "layer_%d_" % i
            p = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            # a layer is recomputed whole, and inside that its mixer and
            # its feed-forward part each again: only the residual stream at
            # the layer boundaries outlives a layer
            x = jax.checkpoint(functools.partial(layer, i))(
                x, p, router_bias[i])

        @jax.checkpoint
        def head(args):     # a block of positions: the logits are wide
            xb, lb = args
            logits = mm("sh,hv->sv", rms(xb, params["final_norm"]),
                        params["lm_head_w"])
            return jnp.sum(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, lb[:, None], 1)[:, 0])

        Pb = _largest_divisor(x.shape[0], 1024)
        return jnp.sum(jax.lax.map(head, (
            x.reshape(-1, Pb, x.shape[1]), labels.reshape(-1, Pb))))

    def loss(params, feed):
        bias = feed.get("router_bias")
        if bias is None:
            bias = jnp.zeros((cfg["num_hidden_layers"],
                              cfg["num_experts_total"]), jnp.float32)
        # one row at a time, written out: under a lax.map the gradient of
        # every leaf would be the loop's carry, held twice
        sums = [row_loss(params, feed["tokens"][r], feed["labels"][r], bias)
                for r in range(feed["tokens"].shape[0])]
        return sum(sums) / feed["tokens"].size

    return loss


def optimizer(cfg):
    return {"lr": cfg["learning_rate"], "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}


# -- the program: the system under test --------------------------------------
class Step(fluid_step.FluidStep):
    """``models.kimi_linear.build_train_program`` under ``fluid.Executor``.
    A feed's ``router_bias`` [layers, num_experts_total], where there is
    one, goes into the routers' frozen bias variables."""

    def __init__(self, cfg, mix):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.models import kimi_linear

        assert cfg["amp"] in ("bfloat16", "off"), cfg["amp"]
        main, startup, loss = kimi_linear.build_train_program(
            kimi_linear.KimiLinearConfig.from_dict(cfg), mix["batch"],
            mix["seq_len"], lr=cfg["learning_rate"],
            use_amp=cfg["amp"] == "bfloat16", recompute=mix["recompute"])
        self.moe_layers = [i for i in range(cfg["num_hidden_layers"])
                           if not _is_dense(cfg, i)]
        # the reference's leaves carry the program's parameter names
        super().__init__(main, startup, loss, fluid.Scope(),
                         {k: k for k in param_shapes(cfg)})

    def run(self, feed):
        if "router_bias" in feed:
            feed = dict(feed)
            bias = np.asarray(feed.pop("router_bias"), "float32")
            for i in self.moe_layers:
                self.scope.set_var("layer_%d_moe_router_bias" % i, bias[i])
        return super().run(feed)


def build(cfg, mix):
    return Step(cfg, mix)


def expected_kernel_tiers(cfg, mix):
    """The Pallas attention tiers the step has to contain: v is narrower
    than q and k, which only the flash tier serves, at every sequence
    length that a 128-row tile divides (``kernels/attention.py``'s tier
    table)."""
    return ("flash", "flash_bwd") if mix["seq_len"] % 128 == 0 else ()
