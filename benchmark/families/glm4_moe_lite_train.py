"""Family ``glm4_moe_lite_train``: next-token training of a GLM-4.7-Flash
block stack (latent attention with a low-rank query and a rotary key part
shared by all heads in every layer; a dense SwiGLU in the leading layer, a
sigmoid-routed expert layer beside a shared expert in the others) and its
multi-token-prediction (MTP) module as a second loss, through
``models.glm4_moe_lite.build_train_program`` and ``fluid.Executor.run``,
one expert-parallel rank's share.

The program's side (``build``) is the system under test; the rest is the
yardstick: weights and feeds from the seed, FLOPs and attention's cost
from shapes, and the plain float32 reference of the same step, which
imports nothing of the program. The reference's leaves carry the
program's parameter names.

The share: the router scores all ``num_experts_total`` experts and keeps
``num_experts_per_tok``; experts ``expert_offset`` .. ``+
n_routed_experts`` are held here and what the absent ones would add is
left out, in the program and in the reference alike; the shared expert is
whole; ids and both losses are over the ``vocab_size`` rows held here. The
routers' selection-only bias gets no gradient and is no leaf: it is zero
on both sides unless a feed carries ``router_bias`` [layers + 1,
num_experts_total] (the tests do; the last row is the MTP layer's; the
program's ``Step`` then sets its frozen variables from it).
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

import compare
import fluid_step

# planted faults of this model's own, for ``reference_loss(fault=...)``
FAULTS = ("no_mtp", "no_rope", "no_experts")
MLA_HEAD_GROUP = 5          # latent-attention heads whose activations are live together
Q_BLOCK = 256               # queries of an attention block live together
MLP_BLOCK = 1024            # positions of an MLP live together
HEAD_BLOCK = 1024           # positions of the head's logits live together


# -- sizes -------------------------------------------------------------------
def tiny(cfg, mix):
    """The CPU rehearsal's preset: toy widths (q and k as wide as v, as
    published, so S = 256 takes the block tier), the mix's ``rehearse``
    shapes, the five layers, both layer kinds and the MTP module kept.
    Proves nothing about the chip."""
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    mix.update(mix["rehearse"])
    cfg.update(vocab_size=512, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
               kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=8,
               v_head_dim=16, n_routed_experts=4, num_experts_total=16,
               moe_intermediate_size=32)
    return cfg, mix


def tokens_per_step(cfg, mix):
    return mix["batch"] * mix["seq_len"]


def _is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def _dims(cfg):
    """H, nope, rope, v, kv_lora_rank, q_lora_rank."""
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["q_lora_rank"])


def _mtp(cfg):
    return cfg["num_nextn_predict_layers"]


def _attention_layers(cfg):
    """Every layer's mixer is latent attention, the MTP module's too."""
    return cfg["num_hidden_layers"] + _mtp(cfg)


def _causal_pairs(s):
    return s * (s + 1) // 2


def flops(cfg, mix):
    """Matmul FLOPs of one training step from shapes, by the qwen family's
    rules: backward = 2 x forward, 2*M*N*K a matmul, nothing recomputed is
    counted, gathers and elementwise work (the rotary embedding among it)
    not counted. Per token forward: every latent-attention projection, the
    dense MLP, the shared expert, the routed experts at the EXPECTED
    ``num_experts_per_tok * n_routed_experts / num_experts_total`` a token
    (even routing), the router and the head; the MTP module's ``eh_proj``
    and its expert layer at every position and its pass through the head
    at the ``seq_len - 1`` it predicts. Causal attention: its ``S (S + 1)
    / 2`` pairs a head and row at ``2 * (dqk + dv)``, in every layer and
    the MTP's."""
    b, s = mix["batch"], mix["seq_len"]
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    H, dn, dr, dv, r, rq = _dims(cfg)
    f, I = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    mla = 2 * h * rq + 2 * rq * H * (dn + dr) + 2 * h * (r + dr) \
        + 2 * r * H * (dn + dv) + 2 * H * dv * h
    share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["num_experts_total"]
    moe = 2 * h * cfg["num_experts_total"] + share * 6 * h * f \
        + cfg["n_shared_experts"] * 6 * h * f
    L = cfg["num_hidden_layers"]
    n_dense = sum(_is_dense(cfg, i) for i in range(L))
    per_token = L * mla + n_dense * 6 * h * I + (L - n_dense) * moe \
        + 2 * h * V
    per_token += _mtp(cfg) * (2 * 2 * h * h + mla + moe)
    head_mtp = _mtp(cfg) * b * (s - 1) * 2 * h * V
    pairs = _attention_layers(cfg) * b * H * _causal_pairs(s) * 2 * (
        dn + dr + dv)
    return 3 * (per_token * tokens_per_step(cfg, mix) + head_mtp + pairs)


def attention_cost(cfg, mix):
    """``(flops, bytes)`` one step's attention kernels need, every layer's
    and the MTP module's, forward and backward, at d = nope + rope for q
    and k and dv for v (256 and 256 as published): the causal pairs'
    matmul FLOPs - forward ``2 (d + dv)`` a pair, dq's pass ``2 (d + dv +
    d)`` (scores, dP, dq), dk/dv's ``2 (d + dv + dv + d)`` (scores, dV, dP,
    dk) - with neither the masked halves of diagonal tiles nor skipped
    tiles nor recomputation counted; q, k, v, o, do, dq, dk, dv once each
    in the 2-byte type."""
    b, s = mix["batch"], mix["seq_len"]
    H, dn, dr, dv, _, _ = _dims(cfg)
    d = dn + dr
    n = _attention_layers(cfg)
    a_pair = 2 * (d + dv) + 2 * (2 * d + dv) + 2 * (2 * d + 2 * dv)
    return (n * b * H * _causal_pairs(s) * a_pair,
            n * b * H * s * (4 * d + 4 * dv) * 2)


# -- weights and feeds from the seed -----------------------------------------
def _layer_shapes(cfg, p, dense):
    h = cfg["hidden_size"]
    H, dn, dr, dv, r, rq = _dims(cfg)
    E, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs, I = f * cfg["n_shared_experts"], cfg["intermediate_size"]
    shapes = {p + "norm1": (h,), p + "norm2": (h,),
              p + "mla_q_a_w": (h, rq), p + "mla_q_a_norm": (rq,),
              p + "mla_q_b_w": (rq, H * (dn + dr)),
              p + "mla_kv_a_w": (h, r + dr), p + "mla_kv_norm": (r,),
              p + "mla_kv_b_w": (r, H * (dn + dv)),
              p + "mla_o_w": (H * dv, h)}
    if dense:
        shapes.update({p + "mlp_gate_w": (h, I), p + "mlp_up_w": (h, I),
                       p + "mlp_down_w": (I, h)})
    else:
        shapes.update({
            p + "moe_router_w": (h, cfg["num_experts_total"]),
            p + "moe_gate_w": (E, h, f), p + "moe_up_w": (E, h, f),
            p + "moe_down_w": (E, f, h),
            p + "moe_shared_gate_w": (h, fs), p + "moe_shared_up_w": (h, fs),
            p + "moe_shared_down_w": (fs, h)})
    return shapes


def param_shapes(cfg):
    """The trainable leaves."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed_tokens": (V, h), "lm_head_w": (h, V),
              "final_norm": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update(_layer_shapes(cfg, "layer_%d_" % i, _is_dense(cfg, i)))
    if _mtp(cfg):
        shapes.update(_layer_shapes(cfg, "mtp_0_", dense=False))
        shapes.update({"mtp_0_enorm": (h,), "mtp_0_hnorm": (h,),
                       "mtp_0_eh_proj_w": (2 * h, h),
                       "mtp_0_head_norm": (h,)})
    return shapes


def init_params(cfg, seed):
    """Every leaf in one jitted call on the device, float32 as the program
    keeps its master weights: N(0, initializer_range); a norm's weight is
    1 + that. Handed back on the HOST: the driver keeps this copy beside
    the reference's three steps for the last norm, and on the device
    (2.83e9 B at the cell's size) it would not leave the reference's
    parameters, moments, gradient and activations room."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = 1.0 + std * x if name.endswith("norm") \
                or name.endswith(("norm1", "norm2")) else std * x
        return out

    return jax.device_get(make(compare.seed_key(seed)))


def feeds(cfg, mix, seed, n):
    """``n`` batches as numpy: ids uniform over the vocabulary slice held
    here, every row full length; the label of a position is the next id
    (the MTP module's target is the label after it). No position input:
    row ``s`` sits at position ``s``."""
    rng = np.random.default_rng([int(seed), 1])
    b, s, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
    out = []
    for _ in range(n):
        ids = rng.integers(0, V, (b, s + 1)).astype("int64")
        out.append({"tokens": ids[:, :-1].copy(), "labels": ids[:, 1:].copy()})
    return out


def half_batch(feed):
    """The planted fault "half of the batch left out". With ONE row it is
    the second half of the row's positions that is left out of the loss:
    the first half's tokens and labels stand in their place (the kimi
    family's form); with more, the second half of the rows."""
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


def reference_loss(cfg, mm, fault=None):
    """``loss(params, feed)`` of the step as the model's ``config.json`` and
    the configuration file's ``assumed`` give it, float32, every matmul
    through ``mm``: ``L_main + mtp_loss_factor * L_mtp``, ``L_main`` the
    mean cross-entropy of the head over every position against ``labels``,
    ``L_mtp`` the MTP module's over every position but the last (MASKED:
    its target ``labels[i + 1]`` is rolled in and its term weighted 0)
    against the label after. Departures for memory at the timed size,
    none changing a number: rows are mapped one at a time; every layer,
    inside it its attention and its feed-forward part, every group of
    ``MLA_HEAD_GROUP`` heads (a head's columns of ``q_b`` and ``kv_b`` and
    its rows of ``o``: heads never mix before it), every block of
    ``Q_BLOCK`` queries, every block of ``MLP_BLOCK`` positions of the
    dense MLP and of the shared expert, every expert and every block of ``HEAD_BLOCK`` positions of
    either head is recomputed on the way back (``jax.checkpoint``). The
    key part shared by all heads is rotated once and broadcast. The expert
    layer is a dense loop over the experts held here, each applied to
    every token under its weight's column. ``fault``: one of ``FAULTS``,
    planted - ``no_mtp`` (the MTP term's weight 0), ``no_rope`` (nothing
    rotated), ``no_experts`` (the routed sum left out)."""
    assert fault in (None,) + FAULTS, fault
    eps = cfg["rms_norm_eps"]
    H, dn, dr, dv, r, rq = _dims(cfg)
    theta = cfg["rope_theta"]
    off, E = cfg.get("expert_offset", 0), cfg["n_routed_experts"]
    top_k, factor = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    L = cfg["num_hidden_layers"]
    lam = 0.0 if fault == "no_mtp" or not _mtp(cfg) \
        else cfg["mtp_loss_factor"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x):        # [S, heads, dr]: rotate-half over all dr channels
        if fault == "no_rope":
            return x
        S = x.shape[0]
        inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def mla(x, p):
        """A group of heads at a time: [G, ...] leading every operand of
        the group function, which returns the group's part of W_o o. The
        low-rank query's first factor, the latent and the shared key part
        are made once."""
        S = x.shape[0]
        per = _largest_divisor(H, max(1, min(MLA_HEAD_GROUP, H // 2)))
        G = H // per
        qa = rms(mm("sh,hr->sr", x, p["mla_q_a_w"]), p["mla_q_a_norm"])
        kva = mm("sh,hk->sk", x, p["mla_kv_a_w"])       # [c | k_r]
        c = rms(kva[:, :r], p["mla_kv_norm"])
        k_r = rope(kva[:, None, r:])                    # [S, 1, dr], once
        Qb = _largest_divisor(S, Q_BLOCK)
        cols = jnp.arange(S)

        @jax.checkpoint
        def group(i):
            # sliced here, from the leaves as they lie: a transposed copy
            # of every layer's weights would be made ahead of the loop
            w_qb = _take(p["mla_q_b_w"], i, per * (dn + dr), 1)
            w_kvb = _take(p["mla_kv_b_w"], i, per * (dn + dv), 1)
            w_o = _take(p["mla_o_w"], i, per * dv, 0)
            q = mm("sr,rk->sk", qa, w_qb).reshape(S, per, dn + dr)
            q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
            kv = mm("sr,rk->sk", c, w_kvb).reshape(S, per, dn + dv)
            # a head's key: its own part, then the part all heads share
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                k_r, (S, per, dr))], -1)
            v = kv[..., dn:]

            @jax.checkpoint
            def q_block(args):
                qb, row0 = args                         # [Qb, per, dn + dr]
                sc = mm("qhd,shd->hqs", qb, k) * (dn + dr) ** -0.5
                rows = row0 + jnp.arange(Qb)
                sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
                return mm("hqs,shd->qhd", jax.nn.softmax(sc, -1), v)

            ctx = jax.lax.map(q_block, (
                q.reshape(S // Qb, Qb, per, dn + dr), jnp.arange(0, S, Qb)))
            return mm("sk,kh->sh", ctx.reshape(S, per * dv), w_o)

        # (the sum is carried outside the recomputed part, as the experts')
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
        score = jax.nn.sigmoid(mm("sh,he->se", x, p["moe_router_w"]))
        _, ids = jax.lax.top_k(score + bias, top_k)     # bias: choice only
        chosen = jnp.sum(jax.nn.one_hot(ids, score.shape[-1]), 1)   # 0/1
        # (the program renormalises by sum + 1e-20: the configuration
        # file's ``assumed``)
        w = score * chosen / (jnp.sum(score * chosen, -1, keepdims=True)
                              + 1e-20) * factor
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
        return routed + mlp_in_blocks(x, p["moe_shared_gate_w"],
                                      p["moe_shared_up_w"],
                                      p["moe_shared_down_w"])

    def layer(dense, x, p, bias):
        u = x + jax.checkpoint(mla)(rms(x, p["norm1"]), p)
        h_ = rms(u, p["norm2"])
        if dense:
            return u + mlp_in_blocks(h_, p["mlp_gate_w"], p["mlp_up_w"],
                                     p["mlp_down_w"])
        return u + jax.checkpoint(moe)(h_, p, bias)

    def leaves(params, pre):
        return {k[len(pre):]: v for k, v in params.items()
                if k.startswith(pre)}

    def head_sum(params, x, labels, weight):
        """sum_i weight_i * CE(W_head x_i, labels_i), a block of
        positions at a time: the logits are wide."""
        @jax.checkpoint
        def block(args):
            xb, lb, wb = args
            logits = mm("sh,hv->sv", xb, params["lm_head_w"])
            return jnp.sum(wb * (jax.nn.logsumexp(logits, -1)
                                 - jnp.take_along_axis(
                                     logits, lb[:, None], 1)[:, 0]))

        Pb = _largest_divisor(x.shape[0], HEAD_BLOCK)
        return jnp.sum(jax.lax.map(block, (
            x.reshape(-1, Pb, x.shape[1]), labels.reshape(-1, Pb),
            weight.reshape(-1, Pb))))

    def row_loss(params, tokens, labels, router_bias):
        """(the main head's sum, the MTP head's sum) over one row."""
        S = tokens.shape[0]
        x = params["embed_tokens"][tokens]
        for i in range(L):
            # a layer is recomputed whole, and inside that its attention
            # and its feed-forward part each again: only the residual
            # stream at the layer boundaries outlives a layer
            x = jax.checkpoint(functools.partial(layer, _is_dense(cfg, i)))(
                x, leaves(params, "layer_%d_" % i), router_bias[i])
        hid = rms(x, params["final_norm"])
        main = head_sum(params, hid, labels, jnp.ones((S,), jnp.float32))
        if not lam:
            return main, 0.0

        @jax.checkpoint
        def module(hid, p, bias):
            e = rms(params["embed_tokens"][labels], p["enorm"])
            m = mm("sk,kh->sh", jnp.concatenate([e, rms(hid, p["hnorm"])],
                                                -1), p["eh_proj_w"])
            m = layer(False, m, p, bias)
            return rms(m, p["head_norm"])

        m = module(hid, leaves(params, "mtp_0_"), router_bias[L])
        # position i predicts labels[i + 1]; the last has no target
        return main, head_sum(params, m, jnp.roll(labels, -1),
                              (jnp.arange(S) < S - 1).astype(jnp.float32))

    def loss(params, feed):
        bias = feed.get("router_bias")
        if bias is None:
            bias = jnp.zeros((L + _mtp(cfg), cfg["num_experts_total"]),
                             jnp.float32)
        B, S = feed["tokens"].shape
        # one row at a time, written out: under a lax.map the gradient of
        # every leaf would be the loop's carry, held twice
        sums = [row_loss(params, feed["tokens"][b], feed["labels"][b], bias)
                for b in range(B)]
        main = sum(s[0] for s in sums) / (B * S)
        if not lam:
            return main
        return main + lam * sum(s[1] for s in sums) / (B * (S - 1))

    return loss


def optimizer(cfg):
    return {"lr": cfg["learning_rate"], "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}


# -- the program: the system under test --------------------------------------
class Step(fluid_step.FluidStep):
    """``models.glm4_moe_lite.build_train_program`` under
    ``fluid.Executor``. A feed's ``router_bias`` [layers + 1,
    num_experts_total], where there is one, goes into the routers' frozen
    bias variables (its last row the MTP layer's)."""

    def __init__(self, cfg, mix):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.models import glm4_moe_lite

        assert cfg["amp"] in ("bfloat16", "off"), cfg["amp"]
        main, startup, loss = glm4_moe_lite.build_train_program(
            glm4_moe_lite.Glm4MoeLiteConfig.from_dict(cfg), mix["batch"],
            mix["seq_len"], lr=cfg["learning_rate"],
            use_amp=cfg["amp"] == "bfloat16", recompute=mix["recompute"])
        L = cfg["num_hidden_layers"]
        self.routers = {i: "layer_%d_moe_router_bias" % i for i in range(L)
                        if not _is_dense(cfg, i)}
        if _mtp(cfg):
            self.routers[L] = "mtp_0_moe_router_bias"
        # the reference's leaves carry the program's parameter names
        super().__init__(main, startup, loss, fluid.Scope(),
                         {k: k for k in param_shapes(cfg)})

    def run(self, feed):
        if "router_bias" in feed:
            feed = dict(feed)
            bias = np.asarray(feed.pop("router_bias"), "float32")
            for i, name in self.routers.items():
                self.scope.set_var(name, bias[i])
        return super().run(feed)


def build(cfg, mix):
    return Step(cfg, mix)


def expected_kernel_tiers(cfg, mix):
    """The Pallas attention tiers the step has to contain, by the
    sequence length (``kernels/attention.py``'s tier table; q, k and v
    are one width here, as published)."""
    s = mix["seq_len"]
    tier = "block" if s <= 1024 else "long" if s <= 4096 else "flash"
    return (tier, tier + "_bwd")
