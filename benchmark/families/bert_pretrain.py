"""Family ``bert_pretrain``: BERT masked-LM pretraining through
``models.bert.build_pretrain_program`` and ``fluid.Executor.run``.

The program's side (``build``) is the system under test; the rest is the
yardstick: weights and feeds from the seed, FLOPs from shapes, and the
plain float32 reference of the same step, which imports nothing of the
program.
"""

import copy
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

import compare
import fluid_step

PER_LAYER = ("q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w", "o_b",
             "ln1_w", "ln1_b", "f1_w", "f1_b", "f2_w", "f2_b",
             "ln2_w", "ln2_b")


# -- sizes -------------------------------------------------------------------
def tiny(cfg, mix):
    """The CPU rehearsal's preset: toy widths, the mix's ``rehearse``
    shapes. Proves nothing about the chip."""
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    mix.update(mix["rehearse"])
    cfg.update(vocab_size=1024, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=128,
               max_position_embeddings=mix["seq_len"])
    return cfg, mix


def tokens_per_step(cfg, mix):
    return mix["batch"] * mix["seq_len"]


def flops(cfg, mix):
    """Matmul FLOPs of one training step from shapes (backward = 2 x
    forward, 2*M*N*K a matmul; gathers and elementwise not counted;
    nothing recomputed is counted). The MLM head runs on the
    ``predictions_per_row`` gathered rows. As ``bench.py`` had it."""
    b, s = mix["batch"], mix["seq_len"]
    h, L, V = (cfg["hidden_size"], cfg["num_hidden_layers"],
               cfg["vocab_size"])
    ffn = cfg["intermediate_size"]
    per_layer = 8 * b * s * h * h + 4 * b * s * h * ffn + 4 * b * s * s * h
    rows = b * mix["predictions_per_row"]
    head = 2 * rows * h * h + 2 * rows * h * V
    return 3 * (L * per_layer + head)


def attention_cost(cfg, mix):
    """``(flops, bytes)`` one step's attention kernels need, all layers,
    forward and backward: QK^T and PV forward, dV, dP, dQ, dK backward
    (12*B*H*S^2*d, recomputation not counted); q, k, v, do read and
    o, dq, dk, dv written once each, in the 2-byte type AMP feeds them."""
    b, s = mix["batch"], mix["seq_len"]
    H = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // H
    L = cfg["num_hidden_layers"]
    return (L * 12 * b * H * s * s * d, L * 8 * b * H * s * d * 2)


# -- weights and feeds from the seed -----------------------------------------
def param_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    per = {"q_w": (h, h), "k_w": (h, h), "v_w": (h, h), "o_w": (h, h),
           "f1_w": (h, f), "f2_w": (f, h),
           "q_b": (h,), "k_b": (h,), "v_b": (h,), "o_b": (h,),
           "f1_b": (f,), "f2_b": (h,),
           "ln1_w": (h,), "ln1_b": (h,), "ln2_w": (h,), "ln2_b": (h,)}
    shapes = {"layers." + k: (L,) + per[k] for k in PER_LAYER}
    shapes.update({
        "word_emb": (V, h),
        "pos_emb": (cfg["max_position_embeddings"], h),
        "sent_emb": (cfg["type_vocab_size"], h),
        "emb_ln_w": (h,), "emb_ln_b": (h,),
        "mlm_w": (h, h), "mlm_b": (h,), "mlm_ln_w": (h,), "mlm_ln_b": (h,),
        "mlm_out_bias": (V,)})
    return shapes


def init_params(cfg, seed):
    """Every leaf in one jitted call on the device, float32 as the
    program keeps its master weights: N(0, initializer_range), layer-norm
    scales 1 + that, so that every leaf takes part in the forward pass."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            is_scale = re.search(r"ln\d?_w$", name) is not None
            out[name] = 1.0 + x if is_scale else x
        return out

    return make(compare.seed_key(seed))


def feeds(cfg, mix, seed, n):
    """``n`` batches as numpy, rows all different. Nine rows in ten are
    full, the rest padded from a quarter of the length up; 15% of a row's
    tokens are predicted, the other prediction slots carry weight 0."""
    rng = np.random.default_rng([int(seed), 1])
    b, s, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
    P = mix["predictions_per_row"]
    out = []
    for _ in range(n):
        lens = np.where(rng.random(b) < mix["full_rows_share"], s,
                        rng.integers(max(P, s // 4), s + 1, b))
        src = rng.integers(0, V, (b, s))
        local = np.stack([rng.permutation(int(n_))[:P] for n_ in lens])
        want = np.maximum(1, np.round(mix["predicted_share"] * lens))
        out.append({
            "src_ids": src.astype("int64"),
            "pos_ids": np.tile(np.arange(s, dtype="int64"), (b, 1)),
            "sent_ids": (np.arange(s)[None, :] >= (lens // 2)[:, None])
            .astype("int64"),
            "input_mask": (np.arange(s)[None, :] < lens[:, None])
            .astype("float32")[:, :, None],
            "mask_pos": (local + np.arange(b)[:, None] * s).astype("int64"),
            "mask_label": rng.integers(0, V, (b, P)).astype("int64"),
            "mask_weight": (np.arange(P)[None, :] < want[:, None])
            .astype("float32")})
    return out


half_batch = compare.half_batch     # the planted fault


# -- the plain reference -----------------------------------------------------
def reference_loss(cfg, mm):
    """``loss(params, feed)`` of BERT's masked-LM step as published
    (Devlin et al. 2018; post-layer-norm encoder, erf GELU, decoder tied
    to the word embeddings), float32, every matmul through ``mm``.
    Layers are scanned and recomputed on the way back so that the timed
    sizes fit beside nothing else."""
    H = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // H

    def layer(x, bias, p):
        B, S, h = x.shape

        def proj(w, b):
            return (mm("bsh,hk->bsk", x, p[w]) + p[b]).reshape(B, S, H, d)

        q, k, v = proj("q_w", "q_b"), proj("k_w", "k_b"), proj("v_w", "v_b")
        sc = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(d) + bias
        pr = jax.nn.softmax(sc, axis=-1)
        ctx = mm("bhqk,bkhd->bqhd", pr, v).reshape(B, S, h)
        x = compare.layer_norm(x + mm("bsh,hk->bsk", ctx, p["o_w"]) + p["o_b"],
                p["ln1_w"], p["ln1_b"])
        f = jax.nn.gelu(mm("bsh,hf->bsf", x, p["f1_w"]) + p["f1_b"],
                        approximate=False)
        f = mm("bsf,fh->bsh", f, p["f2_w"]) + p["f2_b"]
        return compare.layer_norm(x + f, p["ln2_w"], p["ln2_b"])

    def loss(params, feed):
        x = (params["word_emb"][feed["src_ids"]]
             + params["pos_emb"][feed["pos_ids"]]
             + params["sent_emb"][feed["sent_ids"]])
        x = compare.layer_norm(x, params["emb_ln_w"], params["emb_ln_b"])
        mask = feed["input_mask"][:, :, 0]
        bias = ((mask - 1.0) * 1e4)[:, None, None, :]
        stacked = {k: params["layers." + k] for k in PER_LAYER}

        def body(x, p):
            return jax.checkpoint(layer)(x, bias, p), None

        x, _ = jax.lax.scan(body, x, stacked)
        sel = x.reshape(-1, x.shape[-1])[feed["mask_pos"].reshape(-1)]
        t = jax.nn.gelu(mm("ph,hk->pk", sel, params["mlm_w"])
                        + params["mlm_b"], approximate=False)
        t = compare.layer_norm(t, params["mlm_ln_w"], params["mlm_ln_b"])
        logits = mm("ph,vh->pv", t, params["word_emb"]) \
            + params["mlm_out_bias"]
        label = feed["mask_label"].reshape(-1)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, label[:, None], 1)[:, 0]
        w = feed["mask_weight"].reshape(-1)
        return jnp.sum(ce * w) / (jnp.sum(w) + 1e-6)

    return loss


def optimizer(cfg):
    return {"lr": cfg["learning_rate"], "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}


# -- the program: the system under test --------------------------------------
class Step(fluid_step.FluidStep):
    """``models.bert.build_pretrain_program`` under ``fluid.Executor``."""

    def __init__(self, cfg, mix):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.models import bert

        bcfg = bert.BertConfig(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            ffn_hidden=cfg["intermediate_size"],
            max_seq=cfg["max_position_embeddings"],
            type_vocab=cfg["type_vocab_size"],
            hidden_dropout=cfg["hidden_dropout_prob"],
            attn_dropout=cfg["attention_probs_dropout_prob"])
        assert mix["predictions_per_row"] == bert.max_predictions(
            mix["seq_len"]), "the program sizes its MLM head from seq_len"
        assert cfg["amp"] in ("bfloat16", "off"), cfg["amp"]
        main, startup, loss = bert.build_pretrain_program(
            bcfg, seq_len=mix["seq_len"], lr=cfg["learning_rate"],
            use_amp=cfg["amp"] == "bfloat16")
        scope = fluid.Scope()
        super().__init__(main, startup, loss, scope,
                         self._leaf_names(main, cfg))

    @staticmethod
    def _leaf_names(main, cfg):
        """Reference leaf -> the program's variable. Layer norms are
        numbered by a process-wide counter, so they are read off the
        program's ``layer_norm`` ops in order: the embeddings', two a
        layer, the MLM head's."""
        lns = [(op.input("Scale")[0], op.input("Bias")[0])
               for op in main.global_block().ops
               if op.type == "layer_norm"]
        L = cfg["num_hidden_layers"]
        assert len(lns) == 2 * L + 2, len(lns)
        names = {"word_emb": "word_emb", "pos_emb": "pos_emb",
                 "sent_emb": "sent_emb", "mlm_out_bias": "mlm_out_bias",
                 "emb_ln_w": lns[0][0], "emb_ln_b": lns[0][1],
                 "mlm_ln_w": lns[-1][0], "mlm_ln_b": lns[-1][1]}
        fcs = {"attn_q": "q", "attn_k": "k", "attn_v": "v", "attn_out": "o",
               "ffn1": "f1", "ffn2": "f2"}
        # fc parameters are "<name>.w_<n>", n from a process-wide counter
        for p in main.global_block().all_parameters():
            m = re.match(r"^layer_(\d+)_(\w+)\.(w|b)_\d+$", p.name)
            if m:
                names["layers.%s_%s[%s]" % (fcs[m.group(2)], m.group(3),
                                            m.group(1))] = p.name
            m = re.match(r"^mlm_transform\.(w|b)_\d+$", p.name)
            if m:
                names["mlm_" + m.group(1)] = p.name
        for i in range(L):
            for j, ln in enumerate(("ln1", "ln2")):
                names["layers.%s_w[%d]" % (ln, i)] = lns[1 + 2 * i + j][0]
                names["layers.%s_b[%d]" % (ln, i)] = lns[1 + 2 * i + j][1]
        return names


def build(cfg, mix):
    return Step(cfg, mix)


def expected_kernel_tiers(cfg, mix):
    """The Pallas attention tiers the step has to contain (the model's
    ``auto`` takes the fused path from S = 256 up)."""
    return ("block", "block_bwd") if mix["seq_len"] >= 256 else ()
