"""Family ``transformer_nmt``: the encoder-decoder Transformer of Vaswani
et al. 2017, trained teacher-forced through ``models.transformer``: one
eager pass under the dygraph tracer, loss and AMP Adam appended, the
static step run by ``fluid.Executor`` (as ``bench.py:bench_transformer``
built it).

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

ATTN = ("q", "k", "v", "o")
ENC = tuple("enc_%s_%s" % (a, k) for a in ATTN for k in "wb") + tuple(
    "enc_%s_%s" % (n, k) for n in ("f1", "f2", "ln1", "ln2") for k in "wb")
DEC = tuple("dec_%s%s_%s" % (t, a, k) for t in "sc" for a in ATTN
            for k in "wb") + tuple(
    "dec_%s_%s" % (n, k) for n in ("f1", "f2", "ln1", "ln2", "ln3")
    for k in "wb")


# -- sizes -------------------------------------------------------------------
def tiny(cfg, mix):
    """The CPU rehearsal's preset: toy widths, the mix's ``rehearse``
    shapes. Proves nothing about the chip."""
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    mix.update(mix["rehearse"])
    cfg.update(vocab_size=512, d_model=32, h=4, d_ff=64, N=2, max_len=64)
    return cfg, mix


def tokens_per_step(cfg, mix):
    return mix["batch"] * mix["tgt_len"]


def flops(cfg, mix):
    """Matmul FLOPs of one training step from shapes (backward = 2 x
    forward, 2*M*N*K a matmul; nothing recomputed is counted): q, k, v, o
    projections, the two attention matmuls and the FFN a layer, the
    decoder's cross-attention besides, and the vocabulary projection. As
    ``bench.py`` had it, with source and target lengths kept apart."""
    b, s, t = mix["batch"], mix["src_len"], mix["tgt_len"]
    d, di, L, V = cfg["d_model"], cfg["d_ff"], cfg["N"], cfg["vocab_size"]
    proj = lambda rows: 2 * rows * d * d            # noqa: E731
    ffn = lambda rows: 4 * rows * d * di            # noqa: E731
    enc = 4 * proj(b * s) + 4 * b * s * s * d + ffn(b * s)
    dec = (4 * proj(b * t) + 4 * b * t * t * d          # self-attention
           + 2 * proj(b * t) + 2 * proj(b * s) + 4 * b * t * s * d  # cross
           + ffn(b * t))
    head = 2 * b * t * d * V
    return 3 * (L * enc + L * dec + head)


def expected_kernel_tiers(cfg, mix):
    """No Pallas attention in this step: the model's matmul-softmax
    chain is XLA's at these lengths."""
    return ()


# -- weights and feeds from the seed -----------------------------------------
def param_shapes(cfg):
    d, f, L, V = cfg["d_model"], cfg["d_ff"], cfg["N"], cfg["vocab_size"]
    per = {}
    for key in ENC + DEC:
        part, kind = key.split("_")[1], key[-1]
        if part in ("f1", "f2"):
            shape = ((d, f) if part == "f1" else (f, d)) if kind == "w" \
                else ((f,) if part == "f1" else (d,))
        elif part.startswith("ln"):
            shape = (d,)
        else:
            shape = (d, d) if kind == "w" else (d,)
        per["layers." + key] = (L,) + shape
    per.update({"src_emb": (V, d), "tgt_emb": (V, d),
                "pos_emb": (cfg["max_len"], d),
                "proj_w": (d, V), "proj_b": (V,)})
    return per


def init_params(cfg, seed):
    """Every leaf in one jitted call on the device, float32 as the
    program keeps its master weights: matrices and embeddings
    N(0, d_model^-0.5) (so the scaled embedding is of unit size), biases
    N(0, 0.02), layer-norm scales 1 + N(0, 0.02)."""
    shapes = param_shapes(cfg)
    wide = cfg["d_model"] ** -0.5

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            matrix = len(shape) - name.startswith("layers.") == 2
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * (wide if matrix else 0.02)
            is_scale = re.search(r"ln\d_w$", name) is not None
            out[name] = 1.0 + x if is_scale else x
        return out

    return make(compare.seed_key(seed))


def feeds(cfg, mix, seed, n):
    """``n`` batches as numpy, rows all different: full rows of source
    and target ids (the program's training forward takes no padding
    mask), labels drawn apart from the inputs."""
    rng = np.random.default_rng([int(seed), 2])
    b, s, t, V = mix["batch"], mix["src_len"], mix["tgt_len"], \
        cfg["vocab_size"]
    causal = np.triu(np.full((t, t), -1e4, np.float32), k=1)
    out = []
    for _ in range(n):
        out.append({
            "src_ids": rng.integers(1, V, (b, s)).astype("int64"),
            "tgt_ids": rng.integers(1, V, (b, t)).astype("int64"),
            "pos_src": np.tile(np.arange(s, dtype="int64"), (b, 1)),
            "pos_tgt": np.tile(np.arange(t, dtype="int64"), (b, 1)),
            "causal_bias": causal.reshape(1, 1, t, t),
            "label": rng.integers(1, V, (b, t, 1)).astype("int64")})
    return out


half_batch = compare.half_batch     # the planted fault


# -- the plain reference -----------------------------------------------------
def reference_loss(cfg, mm):
    """``loss(params, feed)`` of the Transformer's training step as
    published (post-layer-norm, ReLU FFN, embeddings scaled by
    sqrt(d_model)), with the program's departures: learned positions
    shared by both sides, untied embeddings and projection, no label
    smoothing. float32, every matmul through ``mm``; layers scanned and
    recomputed on the way back."""
    H = cfg["h"]
    d = cfg["d_model"] // H

    def attend(p, pre, xq, xkv, bias):
        B, T, _ = xq.shape
        S = xkv.shape[1]
        q = (mm("bsh,hk->bsk", xq, p[pre + "q_w"]) + p[pre + "q_b"])
        k = (mm("bsh,hk->bsk", xkv, p[pre + "k_w"]) + p[pre + "k_b"])
        v = (mm("bsh,hk->bsk", xkv, p[pre + "v_w"]) + p[pre + "v_b"])
        sc = mm("bqhd,bkhd->bhqk", q.reshape(B, T, H, d),
                k.reshape(B, S, H, d)) / math.sqrt(d)
        if bias is not None:
            sc = sc + bias
        ctx = mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1),
                 v.reshape(B, S, H, d)).reshape(B, T, H * d)
        return mm("bsh,hk->bsk", ctx, p[pre + "o_w"]) + p[pre + "o_b"]

    def ffn(p, pre, x):
        f = jax.nn.relu(mm("bsh,hf->bsf", x, p[pre + "f1_w"])
                        + p[pre + "f1_b"])
        return mm("bsf,fh->bsh", f, p[pre + "f2_w"]) + p[pre + "f2_b"]

    def enc_layer(x, p):
        x = compare.layer_norm(x + attend(p, "enc_", x, x, None),
                p["enc_ln1_w"], p["enc_ln1_b"])
        return compare.layer_norm(x + ffn(p, "enc_", x), p["enc_ln2_w"], p["enc_ln2_b"])

    def dec_layer(x, enc, causal, p):
        x = compare.layer_norm(x + attend(p, "dec_s", x, x, causal),
                p["dec_ln1_w"], p["dec_ln1_b"])
        x = compare.layer_norm(x + attend(p, "dec_c", x, enc, None),
                p["dec_ln2_w"], p["dec_ln2_b"])
        return compare.layer_norm(x + ffn(p, "dec_", x), p["dec_ln3_w"], p["dec_ln3_b"])

    def loss(params, feed):
        scale = math.sqrt(cfg["d_model"])
        x = params["src_emb"][feed["src_ids"]] * scale \
            + params["pos_emb"][feed["pos_src"]]
        x, _ = jax.lax.scan(
            lambda x, p: (jax.checkpoint(enc_layer)(x, p), None), x,
            {k: params["layers." + k] for k in ENC})
        y = params["tgt_emb"][feed["tgt_ids"]] * scale \
            + params["pos_emb"][feed["pos_tgt"]]
        y, _ = jax.lax.scan(
            lambda y, p: (jax.checkpoint(dec_layer)(
                y, x, feed["causal_bias"], p), None), y,
            {k: params["layers." + k] for k in DEC})
        logits = mm("bth,hv->btv", y, params["proj_w"]) + params["proj_b"]
        label = feed["label"][:, :, 0]
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, label[:, :, None], 2)[:, :, 0]
        return jnp.mean(ce)

    return loss


def optimizer(cfg):
    return {"lr": cfg["learning_rate"], "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}


# -- the program: the system under test --------------------------------------
_STRUCTURED = [
    (r"^(enc)_(\d+)\.attn\.(q|k|v|out)_fc\.(weight|bias)$", "enc_"),
    (r"^(dec)_(\d+)\.self_attn\.(q|k|v|out)_fc\.(weight|bias)$", "dec_s"),
    (r"^(dec)_(\d+)\.cross_attn\.(q|k|v|out)_fc\.(weight|bias)$", "dec_c"),
]


def leaf_of(structured):
    """``enc_3.attn.q_fc.weight`` -> ``layers.enc_q_w[3]``."""
    kind = {"weight": "w", "bias": "b"}
    top = {"src_emb.weight": "src_emb", "tgt_emb.weight": "tgt_emb",
           "pos_emb.weight": "pos_emb", "proj.weight": "proj_w",
           "proj.bias": "proj_b"}
    if structured in top:
        return top[structured]
    for pat, pre in _STRUCTURED:
        m = re.match(pat, structured)
        if m:
            return "layers.%s%s_%s[%s]" % (pre, m.group(3)[0],
                                           kind[m.group(4)], m.group(2))
    m = re.match(r"^(enc|dec)_(\d+)\.ffn\.fc(1|2)\.(weight|bias)$",
                 structured)
    if m:
        return "layers.%s_f%s_%s[%s]" % (m.group(1), m.group(3),
                                         kind[m.group(4)], m.group(2))
    m = re.match(r"^(enc|dec)_(\d+)\.ln(\d)\.(weight|bias)$", structured)
    if m:
        return "layers.%s_ln%s_%s[%s]" % (m.group(1), m.group(3),
                                          kind[m.group(4)], m.group(2))
    raise KeyError("no reference leaf for the program's %r" % structured)


class Step(fluid_step.FluidStep):
    def __init__(self, cfg, mix):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import dygraph, layers, optimizer as fopt
        from paddle_tpu.fluid.contrib import mixed_precision
        from paddle_tpu.models import transformer

        V, t = cfg["vocab_size"], mix["tgt_len"]
        assert cfg["amp"] in ("bfloat16", "off"), cfg["amp"]
        shape_feed = feeds(cfg, mix, 0, 1)[0]
        order = ("src_ids", "tgt_ids", "pos_src", "pos_tgt", "causal_bias")
        with dygraph.guard():
            model = transformer.Transformer(
                V, V, d_model=cfg["d_model"], n_heads=cfg["h"],
                d_inner=cfg["d_ff"], n_layers=cfg["N"],
                max_len=cfg["max_len"], dropout_rate=cfg["P_drop"])
            args = [dygraph.to_variable(shape_feed[k]) for k in order]
            _, traced = dygraph.jit.trace(model, args)
            names = {leaf_of(s): p.name
                     for s, p in model.named_parameters()}
        startup = fluid.Program()
        with fluid.program_guard(traced.program, startup):
            logits = traced.program.global_block().var(
                traced._fetch_names[0])
            label = layers.data("tfm_label", [t, 1], dtype="int64")
            ce = layers.softmax_with_cross_entropy(
                layers.reshape(logits, [-1, V]),
                layers.reshape(label, [-1, 1]))
            loss = layers.mean(ce)
            opt = fopt.Adam(learning_rate=cfg["learning_rate"])
            if cfg["amp"] == "bfloat16":
                opt = mixed_precision.decorate(opt)
            opt.minimize(loss)
        traced._materialize_scope()
        feed_names = dict(zip(order, traced._feed_names), label="tfm_label")
        super().__init__(traced.program, startup, loss, traced._scope,
                         names, feed_names)


def build(cfg, mix):
    return Step(cfg, mix)
