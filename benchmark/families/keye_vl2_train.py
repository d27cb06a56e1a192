"""Family ``keye_vl2_train``: next-token training of Keye-VL-2.0's
language block stack (GQA softmax attention under a learned top-k key
selection - a lightning indexer - and a sparse expert layer with no shared
expert, every layer alike) through ``models.keye_vl2.build_train_program``
and ``fluid.Executor.run``, one expert-parallel rank's share.

The program's side (``build``) is the system under test; the rest is the
yardstick: weights and feeds from the seed, FLOPs from shapes, and the
plain float32 reference of the same step, which imports nothing of the
program. The reference's leaves carry the program's parameter names.

The selection is not differentiable, so the indexer's parameters get no
gradient: they are not leaves of the comparison. Both sides make them from
the seed with ``index_params`` - the seed rides in every feed as
``index_seed``, the program's ``Step`` sets its frozen variables from it,
the reference draws them inside its loss.

The share: the router scores all ``num_experts_total`` experts and keeps
``num_experts_per_tok``; experts ``expert_offset`` .. ``+ num_experts`` are
held here and what the absent ones would add is left out, in the program
and in the reference alike; ids and the loss are over the ``vocab_size``
rows held here.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

import compare
import fluid_step

# planted faults of this model's own, for ``reference_loss(fault=...)``
FAULTS = ("dense", "topk_half", "no_experts")


# -- sizes -------------------------------------------------------------------
def tiny(cfg, mix):
    """The CPU rehearsal's preset: toy widths, the mix's ``rehearse``
    shapes, ``topk`` 32 so that most rows select. Float32 (``amp`` off):
    at toy widths a bf16 step moves a boundary key in most rows, and its
    readings then lie ABOVE the fp8 control's (12 CPU seeds, drawn with a
    0.02 embedding: ``grad_mid`` 0.0067-0.0123 against the control's
    0.0122-0.017), so no limit could
    pass the one and fail the other; ``tests/test_keye_vl2.py`` runs the
    bf16 program. Proves nothing about the chip."""
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    mix.update(mix["rehearse"])
    cfg.update(vocab_size=512, hidden_size=64, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               num_experts=4, num_experts_total=16, num_experts_per_tok=3,
               moe_intermediate_size=32, amp="off")
    cfg["rope_scaling"]["mrope_section"] = [4, 6, 6]
    cfg["sa_config"].update(indexer_head_dim=16, indexer_num_heads=4,
                            q_chunk_size=64, kv_chunk_size=64, topk=32)
    return cfg, mix


def tokens_per_step(cfg, mix):
    return mix["batch"] * mix["seq_len"]


def _pairs(cfg, s):
    """``(kept, causal)`` (query, key) pairs a (row, head): a query keeps
    ``min(t + 1, topk)`` of its ``t + 1`` causal keys."""
    k = min(cfg["sa_config"]["topk"], s)
    return k * (k + 1) // 2 + (s - k) * k, s * (s + 1) // 2


def _index_flops(cfg, mix):
    """The indexer's score matmuls over the causal pairs, one forward."""
    sa = cfg["sa_config"]
    return (cfg["num_hidden_layers"] * mix["batch"] * 2
            * sa["indexer_head_dim"] * sa["indexer_num_heads"]
            * _pairs(cfg, mix["seq_len"])[1])


def flops(cfg, mix):
    """Matmul FLOPs of one training step from shapes: backward = 2 x
    forward, 2*M*N*K a matmul, nothing recomputed is counted, gathers and
    elementwise work not counted. Per token forward: the attention's
    projections, the router, the routed experts at the EXPECTED
    ``num_experts_per_tok * num_experts / num_experts_total`` a token
    (uniform routing), the head; attention over the KEPT pairs only
    (``4 * d * H`` a pair), never the masked ones. The indexer has a
    forward and no backward (its selection carries no gradient): its
    projections and its scores over the causal pairs count once."""
    s = mix["seq_len"]
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    L, n = cfg["num_hidden_layers"], tokens_per_step(cfg, mix)
    share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_total"]
    per_token = L * (2 * h * H * d + 2 * 2 * h * Hkv * d + 2 * H * d * h
                     + 2 * h * cfg["num_experts_total"]
                     + share * 6 * h * cfg["moe_intermediate_size"]) \
        + 2 * h * V
    kept = _pairs(cfg, s)[0]
    attention = L * mix["batch"] * 4 * d * H * kept
    indexer = L * n * 2 * h * (Hi * di + di + Hi) + _index_flops(cfg, mix)
    return 3 * (per_token * n + attention) + indexer


def attention_cost(cfg, mix):
    """``(flops, bytes)`` one step's attention kernels need, all layers:
    the matmul FLOPs of the KEPT pairs only, forward 4*d*H a pair and
    backward 2.5 times that (recomputation not counted, nor the masked
    pairs a dense kernel computes); q, do read and o, dq written at H
    heads, k, v read and dk, dv written at the KV head count, once each in
    the 2-byte type, and the selection's bytes read once a kernel."""
    b, s = mix["batch"], mix["seq_len"]
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    L = cfg["num_hidden_layers"]
    return (L * b * 3.5 * 4 * d * H * _pairs(cfg, s)[0],
            L * b * (4 * (H + Hkv) * s * d * 2 + 3 * s * s))


def index_cost(cfg, mix):
    """``(flops, bytes)`` one step's selections need, all layers, one
    forward (recomputation not counted): the scores' matmuls over the
    causal pairs; qI, kI in the 2-byte type and wI read, the selection
    written, once each."""
    b, s = mix["batch"], mix["seq_len"]
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return (_index_flops(cfg, mix), cfg["num_hidden_layers"] * b * (
        s * (Hi * di + di + Hi) * 2 + s * s))


# -- weights and feeds from the seed -----------------------------------------
def param_shapes(cfg):
    """The trainable leaves."""
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed_tokens": (V, h), "lm_head_w": (h, V),
              "final_norm": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer_%d_" % i
        shapes.update({
            p + "norm1": (h,), p + "norm2": (h,),
            p + "attn_q_w": (h, H * d), p + "attn_k_w": (h, Hkv * d),
            p + "attn_v_w": (h, Hkv * d), p + "attn_o_w": (H * d, h),
            p + "attn_q_norm": (d,), p + "attn_k_norm": (d,),
            p + "moe_router_w": (h, cfg["num_experts_total"]),
            p + "moe_gate_w": (E, h, f), p + "moe_up_w": (E, h, f),
            p + "moe_down_w": (E, f, h)})
    return shapes


def index_shapes(cfg):
    """The indexer's leaves: used by the forward, never trained."""
    h = cfg["hidden_size"]
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    shapes = {}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer_%d_attn_idx_" % i
        shapes.update({p + "q_w": (h, Hi * di), p + "k_w": (h, di),
                       p + "weights_w": (h, Hi), p + "k_norm_w": (di,),
                       p + "k_norm_b": (di,)})
    return shapes


def _draw(shapes, key, std, embedding_std=None):
    """N(0, std) a leaf; a norm's weight (a name that ends in ``norm``,
    ``norm1``, ``norm2`` or ``norm_w``) is 1 + that; ``embed_tokens``
    N(0, embedding_std)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name == "embed_tokens":
            out[name] = embedding_std * x
        elif name.endswith(("norm", "norm1", "norm2", "norm_w")):
            out[name] = 1.0 + std * x
        else:
            out[name] = std * x
    return out


def init_params(cfg, seed):
    """Every trainable leaf in one jitted call on the device, float32 as
    the program keeps its master weights. The embedding is drawn at
    ``embedding_std`` (1: the configuration file's ``assumed`` says why),
    everything else at ``initializer_range``."""
    return jax.jit(functools.partial(
        _draw, param_shapes(cfg), std=cfg["initializer_range"],
        embedding_std=cfg["embedding_std"]))(compare.seed_key(seed))


def index_params(cfg, index_seed):
    """The indexer's leaves from a feed's ``index_seed`` (two int32: the
    seed's low 31 bits and the rest); traceable."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(index_seed[0]), index_seed[1]), 0x1D)
    return _draw(index_shapes(cfg), key, cfg["initializer_range"])


def feeds(cfg, mix, seed, n):
    """``n`` batches as numpy: ids uniform over the vocabulary slice held
    here, every row full length; the label of a position is the next id;
    text positions (three equal rows); the seed for the indexer's leaves."""
    rng = np.random.default_rng([int(seed), 1])
    b, s, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
    positions = np.broadcast_to(np.arange(s, dtype="int64"), (3, b, s))
    index_seed = np.array([int(seed) & 0x7FFFFFFF, int(seed) >> 31], "int32")
    out = []
    for _ in range(n):
        ids = rng.integers(0, V, (b, s + 1)).astype("int64")
        out.append({"tokens": ids[:, :-1].copy(), "labels": ids[:, 1:].copy(),
                    "positions": positions.copy(), "index_seed": index_seed})
    return out


def half_batch(feed):
    """The planted fault "half of the batch left out". A batch of one row
    has no half of rows: the row's first half of positions stands in its
    second half's place (ids and labels), so the mean is over the first."""
    if feed["tokens"].shape[0] > 1:
        out = compare.half_batch(
            {k: v for k, v in feed.items() if k in ("tokens", "labels")},
            rows_of="tokens")
        return dict(feed, **out)
    out = dict(feed)
    for k in ("tokens", "labels"):
        v = np.array(feed[k])
        h = v.shape[1] // 2
        v[:, h:2 * h] = v[:, :h]
        out[k] = v
    return out


# -- the plain reference -----------------------------------------------------
def _largest_divisor(n, most):
    return max(d for d in range(1, most + 1) if n % d == 0)


def reference_loss(cfg, mm, fault=None):
    """``loss(params, feed)`` of the step as the model's ``config.json``
    and the descriptions the configuration's ``assumed`` names give it,
    float32, every matmul through ``mm``. Departures, each for memory at
    the timed size and none changing a number: rows are mapped one at a
    time; every layer, every block of queries (the indexer's scores, the
    threshold and the selected softmax of a block together), every expert
    and every block of the head's positions is recomputed on the way back
    (``jax.checkpoint``). The threshold of a row is the ``topk``-th
    largest of its causal scores by a sort; every key that reaches it is
    kept. The expert layer is a dense loop over the experts held here,
    each applied to every token under a 0/1 mask. No alignment loss for
    the indexer and no auxiliary router loss (the config has no key for
    either). ``fault``: one of ``FAULTS``, planted."""
    assert fault in (None,) + FAULTS, fault
    eps = cfg["rms_norm_eps"]
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    topk = sa["topk"] // 2 if fault == "topk_half" else sa["topk"]
    sections = cfg["rope_scaling"]["mrope_section"]
    off, E = cfg.get("expert_offset", 0), cfg["num_experts"]
    top_e = cfg["num_experts_per_tok"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):
        """x [S, heads, n], pos [S, n / 2] the position each frequency
        pair turns by: rotate-half over the whole head."""
        n = x.shape[-1]
        inv = 1.0 / (cfg["rope_theta"]
                     ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
        ang = (pos.astype(jnp.float32) * inv)[:, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :n // 2], x[..., n // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def attention(x, p, pos):
        """``pos`` [3, S]: time, height, width."""
        S = x.shape[0]
        G = H // Hkv
        q = rms(mm("sh,hk->sk", x, p["attn_q_w"]).reshape(S, H, d),
                p["attn_q_norm"])
        k = rms(mm("sh,hk->sk", x, p["attn_k_w"]).reshape(S, Hkv, d),
                p["attn_k_norm"])
        v = mm("sh,hk->sk", x, p["attn_v_w"]).reshape(S, Hkv, d)
        # frequency pair f turns by the row of its section
        row = np.repeat(np.arange(3), sections)
        pos_mrope = jnp.take(pos, row, axis=0).T            # [S, d / 2]
        q = rope(q, pos_mrope).reshape(S, Hkv, G, d)
        k = rope(k, pos_mrope)
        # the indexer: one shared key head, rotary by the first row
        qi = mm("sh,hk->sk", x, p["attn_idx_q_w"]).reshape(S, Hi, di)
        ki = compare.layer_norm(mm("sh,hk->sk", x, p["attn_idx_k_w"]),
                                p["attn_idx_k_norm_w"],
                                p["attn_idx_k_norm_b"], eps)
        wi = mm("sh,hj->sj", x, p["attn_idx_weights_w"])
        pos_index = jnp.broadcast_to(pos[0][:, None], (S, di // 2))
        qi = rope(qi, pos_index)
        ki = rope(ki[:, None, :], pos_index)[:, 0]
        Qb = _largest_divisor(S, 512)
        cols = jnp.arange(S)

        @jax.checkpoint
        def q_block(args):
            qb, qib, wib, row0 = args               # [Qb, Hkv, G, d] ...
            causal = cols[None, :] <= (row0 + jnp.arange(Qb))[:, None]
            if fault == "dense" or topk >= S:
                keep = causal
            else:
                index = jnp.sum(jax.nn.relu(mm("qjd,sd->qjs", qib, ki))
                                * wib[:, :, None], 1)       # [Qb, S]
                index = jnp.where(causal, index, -jnp.inf)
                kth = jnp.sort(index, -1)[:, S - topk][:, None]
                keep = causal & (index >= kth)
            sc = mm("qngd,snd->ngqs", qb, k) * d ** -0.5
            sc = jnp.where(keep[None, None], sc, -jnp.inf)
            return mm("ngqs,snd->qngd", jax.nn.softmax(sc, -1), v)

        ctx = jax.lax.map(q_block, (
            q.reshape(S // Qb, Qb, Hkv, G, d), qi.reshape(S // Qb, Qb, Hi, di),
            wi.reshape(S // Qb, Qb, Hi), jnp.arange(0, S, Qb)))
        return mm("sk,kh->sh", ctx.reshape(S, H * d), p["attn_o_w"])

    def expert(x, wg, wu, wd):
        return mm("sf,fh->sh", jax.nn.silu(mm("sh,hf->sf", x, wg))
                  * mm("sh,hf->sf", x, wu), wd)

    def moe(x, p):
        if fault == "no_experts":
            return jnp.zeros_like(x)
        prob = jax.nn.softmax(mm("sh,he->se", x, p["moe_router_w"]), -1)
        _, ids = jax.lax.top_k(prob, top_e)
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
        return routed

    def layer(x, p, pos):
        u = x + attention(rms(x, p["norm1"]), p, pos)
        return u + moe(rms(u, p["norm2"]), p)

    def row_loss(params, tokens, labels, pos):
        x = params["embed_tokens"][tokens]
        for i in range(cfg["num_hidden_layers"]):
            pre = "layer_%d_" % i
            p = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            x = jax.checkpoint(layer)(x, p, pos)

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
        # the indexer's leaves: from the seed, no leaves of the comparison
        params = dict(params, **jax.lax.stop_gradient(
            index_params(cfg, feed["index_seed"])))
        # one row at a time, written out: under a lax.map the gradient of
        # every leaf would be the loop's carry, held twice
        sums = [row_loss(params, feed["tokens"][r], feed["labels"][r],
                         feed["positions"][:, r])
                for r in range(feed["tokens"].shape[0])]
        return sum(sums) / feed["tokens"].size

    return loss


def optimizer(cfg):
    return {"lr": cfg["learning_rate"], "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}


# -- the program: the system under test --------------------------------------
class Step(fluid_step.FluidStep):
    """``models.keye_vl2.build_train_program`` under ``fluid.Executor``.
    The indexer's frozen variables follow the feed's ``index_seed``."""

    _index_seed = None

    def __init__(self, cfg, mix):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.models import keye_vl2

        assert cfg["amp"] in ("bfloat16", "off"), cfg["amp"]
        main, startup, loss = keye_vl2.build_train_program(
            keye_vl2.KeyeVL2Config.from_dict(cfg), mix["batch"],
            mix["seq_len"], lr=cfg["learning_rate"],
            use_amp=cfg["amp"] == "bfloat16", recompute=mix["recompute"])
        self.index_params = jax.jit(functools.partial(index_params, cfg))
        # the reference's leaves carry the program's parameter names
        super().__init__(main, startup, loss, fluid.Scope(),
                         {k: k for k in param_shapes(cfg)})

    def reset(self):
        self._index_seed = None         # the startup program redraws them
        super().reset()

    def run(self, feed):
        feed = dict(feed)
        seed = tuple(int(n) for n in feed.pop("index_seed"))
        if seed != self._index_seed:
            for name, value in self.index_params(
                    np.array(seed, "int32")).items():
                old = self.scope.find_var(name)
                assert tuple(old.shape) == tuple(value.shape), name
                self.scope.set_var(name, value)
            self._index_seed = seed
        return super().run(feed)


def build(cfg, mix):
    return Step(cfg, mix)


def expected_kernel_tiers(cfg, mix):
    """The Pallas attention tier the step has to contain: ``select`` (its
    three kernels count under one tier) wherever a 128-row tile divides
    the sequence (``kernels/attention.py``'s tier table)."""
    return ("select",) if mix["seq_len"] % 128 == 0 else ()
