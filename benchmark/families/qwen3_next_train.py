"""Family ``qwen3_next_train``: next-token training of a Qwen3-Next block
stack (Gated DeltaNet x3 : gated GQA attention x1, each with a sparse
expert layer) through ``models.qwen3_next.build_train_program`` and
``fluid.Executor.run``, one expert-parallel rank's share.

The program's side (``build``) is the system under test; the rest is the
yardstick: weights and feeds from the seed, FLOPs from shapes, and the
plain float32 reference of the same step, which imports nothing of the
program. The reference's leaves carry the program's parameter names.

The share: the router scores all ``num_experts_total`` experts and keeps
``num_experts_per_tok``; experts ``expert_offset`` .. ``+ num_experts`` are
held here and what the absent ones would add is left out, in the program
and in the reference alike; ids and the loss are over the ``vocab_size``
rows held here.
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
FAULTS = ("no_routed_experts", "g_zero")
RECURRENCE_BLOCK = 64       # recurrence steps recomputed together


# -- sizes -------------------------------------------------------------------
def tiny(cfg, mix):
    """The CPU rehearsal's preset: toy widths, the mix's ``rehearse``
    shapes. Proves nothing about the chip."""
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    mix.update(mix["rehearse"])
    cfg.update(vocab_size=512, hidden_size=64, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               num_experts=4, num_experts_total=16, num_experts_per_tok=3,
               moe_intermediate_size=32, shared_expert_intermediate_size=32)
    return cfg, mix


def tokens_per_step(cfg, mix):
    return mix["batch"] * mix["seq_len"]


def _is_full(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def _dims(cfg):
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return Hk, Hv, dk, dv, Hk * dk, Hv * dv


def flops(cfg, mix):
    """Matmul FLOPs of one training step from shapes: backward = 2 x
    forward, 2*M*N*K a matmul, nothing recomputed is counted, gathers and
    elementwise work not counted. Per token forward: every projection and
    the head; the routed experts at the EXPECTED ``num_experts_per_tok *
    num_experts / num_experts_total`` a token (uniform routing), the
    shared expert and both routers; causal attention at half of S^2
    (``4*S*H*d / 2``); DeltaNet's recurrence at ``3 * 2 * dk * dv`` a value
    head (decay-and-read, the rank-one write, the output read) - the
    recurrence's own count, not the chunked form's, which does more."""
    s = mix["seq_len"]
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    Hk, Hv, dk, dv, kd, vd = _dims(cfg)
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    gdn = 2 * h * (2 * kd + 2 * vd) + 2 * h * 2 * Hv + 2 * vd * h \
        + 6 * dk * dv * Hv
    attn = 2 * h * H * 2 * d + 2 * 2 * h * Hkv * d + 2 * H * d * h \
        + 2 * s * H * d
    share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_total"]
    moe = 2 * h * cfg["num_experts_total"] + share * 6 * h * f \
        + 6 * h * fs + 2 * h
    L = cfg["num_hidden_layers"]
    n_full = sum(_is_full(cfg, i) for i in range(L))
    per_token = (L - n_full) * gdn + n_full * attn + L * moe + 2 * h * V
    return 3 * per_token * tokens_per_step(cfg, mix)


def attention_cost(cfg, mix):
    """``(flops, bytes)`` one step's attention kernels need, all attention
    layers, forward and backward, CAUSAL: half of 12*B*H*S^2*d
    (recomputation not counted, nor the masked half the kernels still
    compute); q, do read and o, dq written at H heads, k, v read and dk,
    dv written at the KV head count, once each in the 2-byte type."""
    b, s = mix["batch"], mix["seq_len"]
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n = sum(_is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return (n * 6 * b * H * s * s * d, n * 4 * b * (H + Hkv) * s * d * 2)


def gdn_cost(cfg, mix):
    """``(flops, bytes)`` one step's DeltaNet cores need, all DeltaNet
    layers, forward and backward (backward = 2 x forward): the matmuls of
    the chunked form at chunk C a chunk and value head - k k^T and q k^T
    (2*C*C*dk each), the triangular system applied to [v | k] (2*C*C*(dv +
    dk)), the local output (2*C*C*dv) and the three products with the
    state (2*C*dk*dv each); the inversion itself is not counted. Bytes: q,
    k, v, o in the 2-byte type and g, beta in f32, read or written once,
    and their cotangents."""
    b, s = mix["batch"], mix["seq_len"]
    Hk, Hv, dk, dv, _, _ = _dims(cfg)
    C = cfg.get("gdn_chunk_size", 64)
    n = sum(not _is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    a_chunk = 2 * C * (2 * C * dk + C * (dv + dk) + C * dv + 3 * dk * dv)
    chunks = b * Hv * -(-s // C)
    nbytes = b * s * ((2 * Hk * dk + 2 * Hv * dv) * 2 + 2 * Hv * 4)
    return (n * 3 * a_chunk * chunks, n * 2 * nbytes)


# -- weights and feeds from the seed -----------------------------------------
def param_shapes(cfg):
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    Hk, Hv, dk, dv, kd, vd = _dims(cfg)
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    shapes = {"embed_tokens": (V, h), "lm_head_w": (h, V),
              "final_norm": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer_%d_" % i
        shapes.update({p + "norm1": (h,), p + "norm2": (h,)})
        if _is_full(cfg, i):
            shapes.update({
                p + "attn_q_w": (h, H * 2 * d), p + "attn_k_w": (h, Hkv * d),
                p + "attn_v_w": (h, Hkv * d), p + "attn_o_w": (H * d, h),
                p + "attn_q_norm": (d,), p + "attn_k_norm": (d,)})
        else:
            shapes.update({
                p + "gdn_qkvz_w": (h, 2 * kd + 2 * vd),
                p + "gdn_ba_w": (h, 2 * Hv),
                p + "gdn_conv_w": (2 * kd + vd,
                                   cfg["linear_conv_kernel_dim"]),
                p + "gdn_a_log": (Hv,), p + "gdn_dt_bias": (Hv,),
                p + "gdn_norm": (dv,), p + "gdn_out_w": (vd, h)})
        shapes.update({
            p + "moe_router_w": (h, cfg["num_experts_total"]),
            p + "moe_gate_w": (E, h, f), p + "moe_up_w": (E, h, f),
            p + "moe_down_w": (E, f, h),
            p + "moe_shared_gate_w": (h, fs), p + "moe_shared_up_w": (h, fs),
            p + "moe_shared_down_w": (fs, h),
            p + "moe_shared_router_w": (h, 1)})
    return shapes


def init_params(cfg, seed):
    """Every leaf in one jitted call on the device, float32 as the program
    keeps its master weights: N(0, initializer_range); a zero-centred norm
    weight is that as it is (it reads as 1 + w), the DeltaNet's plain
    gated-norm weight and ``dt_bias`` are 1 + that, and ``A_log`` is spread
    between log(1/16) and log(16) (a clipped normal), so that a layer's
    heads span slow and fast decay (the family draws A from U(0, 16))."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name.endswith("gdn_a_log"):
                out[name] = math.log(16.0) * jnp.clip(x / 2.0, -1.0, 1.0)
            elif name.endswith(("gdn_norm", "gdn_dt_bias")):
                out[name] = 1.0 + std * x
            else:
                out[name] = std * x
        return out

    return make(compare.seed_key(seed))


def feeds(cfg, mix, seed, n):
    """``n`` batches as numpy: ids uniform over the vocabulary slice held
    here, every row full length; the label of a position is the next id."""
    rng = np.random.default_rng([int(seed), 1])
    b, s, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
    out = []
    for _ in range(n):
        ids = rng.integers(0, V, (b, s + 1)).astype("int64")
        out.append({"tokens": ids[:, :-1].copy(), "labels": ids[:, 1:].copy()})
    return out


half_batch = functools.partial(compare.half_batch, rows_of="tokens")


# -- the plain reference -----------------------------------------------------
def _largest_divisor(n, most):
    return max(d for d in range(1, most + 1) if n % d == 0)


def reference_loss(cfg, mm, fault=None):
    """``loss(params, feed)`` of the step as the model's ``config.json``
    and the family's published description give it, float32, every matmul
    through ``mm``. Departures from that description, each for memory at
    the timed size and none changing a number: rows are mapped one at a
    time; every layer, every block of ``RECURRENCE_BLOCK`` recurrence
    steps, every block of queries, every expert and every block of the
    head's positions is recomputed on the way back (``jax.checkpoint``). The DeltaNet is the recurrence, step
    by step. The expert layer is a dense loop over the experts held here,
    each applied to every token under a 0/1 mask. No multi-token
    prediction module and no auxiliary router loss (the config has no key
    for either). ``fault``: one of ``FAULTS``, planted."""
    assert fault in (None,) + FAULTS, fault
    eps = cfg["rms_norm_eps"]
    Hk, Hv, dk, dv, kd, vd = _dims(cfg)
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    K = cfg["linear_conv_kernel_dim"]
    rd = int(d * cfg["partial_rotary_factor"])
    off, E = cfg.get("expert_offset", 0), cfg["num_experts"]
    top_k = cfg["num_experts_per_tok"]

    def rms(x, w, zero_centered=True):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * (1.0 + w if zero_centered else w)

    def l2norm(x):      # the family's: x * rsqrt(sum(x^2) + 1e-6)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def recurrence(q, k, v, g, beta):
        """q, k [S, Hv, dk], v [S, Hv, dv], g, beta [S, Hv] -> [S, Hv, dv]:
        S' = exp(g_t) S; d = beta_t (v_t - S'^T k_t); S = S' + k_t d^T;
        o_t = S^T q_t."""
        S = q.shape[0]
        pad = (-S) % RECURRENCE_BLOCK   # beta = 0, g = 0: state untouched
        xs = [jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
              for t in (q, k, v, g, beta)]
        xs = [t.reshape((-1, RECURRENCE_BLOCK) + t.shape[1:]) for t in xs]

        def step(state, x):
            q_t, k_t, v_t, g_t, b_t = x
            decayed = state * jnp.exp(g_t)[:, None, None]
            delta = b_t[:, None] * (v_t - mm("hkv,hk->hv", decayed, k_t))
            state = decayed + mm("hk,hv->hkv", k_t, delta)
            return state, mm("hkv,hk->hv", state, q_t)

        @jax.checkpoint
        def block(state, x):
            return jax.lax.scan(step, state, x, unroll=8)

        _, o = jax.lax.scan(block, jnp.zeros((Hv, dk, dv), jnp.float32),
                            tuple(xs))
        return o.reshape((-1, Hv, dv))[:S]

    def delta_net(x, p):
        S = x.shape[0]
        qkvz = mm("sh,hk->sk", x, p["gdn_qkvz_w"])      # [q | k | v | z]
        ba = mm("sh,hk->sk", x, p["gdn_ba_w"])          # [b | a]
        qkv, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
        # causal depthwise convolution, K taps, no bias, then SiLU
        padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(padded[j:j + S] * p["gdn_conv_w"][:, j]
                              for j in range(K)))
        q = qkv[:, :kd].reshape(S, Hk, dk)
        k = qkv[:, kd:2 * kd].reshape(S, Hk, dk)
        v = qkv[:, 2 * kd:].reshape(S, Hv, dv)
        beta = jax.nn.sigmoid(ba[:, :Hv])
        g = -jnp.exp(p["gdn_a_log"]) * jax.nn.softplus(
            ba[:, Hv:] + p["gdn_dt_bias"])
        if fault == "g_zero":
            g = jnp.zeros_like(g)
        # each key head serves Hv / Hk consecutive value heads
        q = jnp.repeat(l2norm(q), Hv // Hk, axis=1) * dk ** -0.5
        k = jnp.repeat(l2norm(k), Hv // Hk, axis=1)
        o = recurrence(q, k, v, g, beta)
        o = rms(o, p["gdn_norm"], zero_centered=False) \
            * jax.nn.silu(z.reshape(S, Hv, dv))
        return mm("sk,kh->sh", o.reshape(S, vd), p["gdn_out_w"])

    def rope(x):        # [S, heads, d]: rotate-half on the first rd dims
        S = x.shape[0]
        inv = 1.0 / (cfg["rope_theta"]
                     ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                x[..., rd:]], -1)

    def attention(x, p):
        S = x.shape[0]
        G = H // Hkv
        qg = mm("sh,hk->sk", x, p["attn_q_w"]).reshape(S, H, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]              # [q | gate] a head
        k = mm("sh,hk->sk", x, p["attn_k_w"]).reshape(S, Hkv, d)
        v = mm("sh,hk->sk", x, p["attn_v_w"]).reshape(S, Hkv, d)
        q = rope(rms(q, p["attn_q_norm"])).reshape(S, Hkv, G, d)
        k = rope(rms(k, p["attn_k_norm"]))
        Qb = _largest_divisor(S, 512)
        cols = jnp.arange(S)

        @jax.checkpoint
        def q_block(args):
            qb, row0 = args                             # [Qb, Hkv, G, d]
            sc = mm("qngd,snd->ngqs", qb, k) * d ** -0.5
            rows = row0 + jnp.arange(Qb)
            sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
            return mm("ngqs,snd->qngd", jax.nn.softmax(sc, -1), v)

        ctx = jax.lax.map(q_block, (q.reshape(S // Qb, Qb, Hkv, G, d),
                                    jnp.arange(0, S, Qb)))
        ctx = ctx.reshape(S, H * d) * jax.nn.sigmoid(gate.reshape(S, H * d))
        return mm("sk,kh->sh", ctx, p["attn_o_w"])

    def expert(x, wg, wu, wd):
        return mm("sf,fh->sh", jax.nn.silu(mm("sh,hf->sf", x, wg))
                  * mm("sh,hf->sf", x, wu), wd)

    def moe(x, p):
        prob = jax.nn.softmax(mm("sh,he->se", x, p["moe_router_w"]), -1)
        _, ids = jax.lax.top_k(prob, top_k)
        chosen = jnp.sum(jax.nn.one_hot(ids, prob.shape[-1]), 1)    # 0/1
        w = prob * chosen / jnp.sum(prob * chosen, -1, keepdims=True)
        held = w[:, off:off + E]                        # [S, E]

        @jax.checkpoint
        def one(wg, wu, wd, col):
            return col[:, None] * expert(x, wg, wu, wd)

        # (the sum is carried outside the recomputed part: a carry inside
        # it would be kept once an expert)
        routed, _ = jax.lax.scan(
            lambda acc, e: (acc + one(*e), None), jnp.zeros_like(x),
            (p["moe_gate_w"], p["moe_up_w"], p["moe_down_w"], held.T))
        if fault == "no_routed_experts":
            routed = jnp.zeros_like(x)
        shared = expert(x, p["moe_shared_gate_w"], p["moe_shared_up_w"],
                        p["moe_shared_down_w"])
        return routed + jax.nn.sigmoid(
            mm("sh,ho->so", x, p["moe_shared_router_w"])) * shared

    def layer(i, x, p):
        mixer = attention if _is_full(cfg, i) else delta_net
        u = x + mixer(rms(x, p["norm1"]), p)
        return u + moe(rms(u, p["norm2"]), p)

    def row_loss(params, tokens, labels):
        x = params["embed_tokens"][tokens]
        for i in range(cfg["num_hidden_layers"]):
            pre = "layer_%d_" % i
            p = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            x = jax.checkpoint(functools.partial(layer, i))(x, p)

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
        # one row at a time, written out: under a lax.map the gradient of
        # every leaf would be the loop's carry, held twice
        sums = [row_loss(params, feed["tokens"][r], feed["labels"][r])
                for r in range(feed["tokens"].shape[0])]
        return sum(sums) / feed["tokens"].size

    return loss


def optimizer(cfg):
    return {"lr": cfg["learning_rate"], "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}


# -- the program: the system under test --------------------------------------
class Step(fluid_step.FluidStep):
    """``models.qwen3_next.build_train_program`` under ``fluid.Executor``."""

    def __init__(self, cfg, mix):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.models import qwen3_next

        assert cfg["amp"] in ("bfloat16", "off"), cfg["amp"]
        main, startup, loss = qwen3_next.build_train_program(
            qwen3_next.Qwen3NextConfig.from_dict(cfg), mix["batch"],
            mix["seq_len"], lr=cfg["learning_rate"],
            use_amp=cfg["amp"] == "bfloat16", recompute=mix["recompute"])
        # the reference's leaves carry the program's parameter names
        super().__init__(main, startup, loss, fluid.Scope(),
                         {k: k for k in param_shapes(cfg)})


def build(cfg, mix):
    return Step(cfg, mix)


def expected_kernel_tiers(cfg, mix):
    """The Pallas attention tiers the step has to contain, by the
    sequence length (``kernels/attention.py``'s tier table)."""
    s = mix["seq_len"]
    tier = "block" if s <= 1024 else "long" if s <= 4096 else "flash"
    return (tier, tier + "_bwd")
