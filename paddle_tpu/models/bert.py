"""BERT/ERNIE-style transformer encoder for MLM pretraining —
BASELINE.json `configs` entry 3 (the Fleet-collective workload).

Parity: the reference trains ERNIE/BERT through its transformer building
blocks (``tests/unittests/dist_transformer.py``, multihead attention as the
fused inference pass ``ir/multihead_matmul_fuse_pass.cc`` recognizes);
built here from the fluid layer surface. TPU notes: attention and FFN
matmuls are kept as single large [B*S, H] GEMMs feeding the MXU; masking is
additive (no dynamic shapes); everything jit-compiles to one XLA program.
"""

import math

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, optimizer


class BertConfig:
    def __init__(self, vocab_size=30522, hidden=768, n_layers=12, n_heads=12,
                 ffn_hidden=3072, max_seq=512, type_vocab=2,
                 hidden_dropout=0.1, attn_dropout=0.1, tp_axis=None):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.ffn_hidden = ffn_hidden
        self.max_seq = max_seq
        self.type_vocab = type_vocab
        self.hidden_dropout = hidden_dropout
        self.attn_dropout = attn_dropout
        # set to a mesh axis name (e.g. "tp") to lay attention/FFN weights
        # out Megatron-style via ParamAttr(shard=...) — see _tp_attr
        self.tp_axis = tp_axis

    @staticmethod
    def base():
        return BertConfig()

    @staticmethod
    def tiny():
        return BertConfig(vocab_size=1024, hidden=64, n_layers=2, n_heads=4,
                          ffn_hidden=128, max_seq=64)


def _tp_attr(cfg, kind):
    """Megatron TP layouts when cfg.tp_axis is set: column-parallel for
    qkv/ffn-in (shard the output features), row-parallel for the
    projections back to hidden (shard the input features); GSPMD derives
    the all-reduce after each row-parallel matmul from these layouts."""
    axis = getattr(cfg, "tp_axis", None)
    if not axis:
        return None
    spec = (None, axis) if kind == "col" else (axis, None)
    return fluid.ParamAttr(shard=spec)


def _mha(x, attn_bias, cfg, prefix):
    h, n_heads = cfg.hidden, cfg.n_heads
    d = h // n_heads
    q = layers.fc(x, h, num_flatten_dims=2, name=prefix + "_q",
                  param_attr=_tp_attr(cfg, "col"))
    k = layers.fc(x, h, num_flatten_dims=2, name=prefix + "_k",
                  param_attr=_tp_attr(cfg, "col"))
    v = layers.fc(x, h, num_flatten_dims=2, name=prefix + "_v",
                  param_attr=_tp_attr(cfg, "col"))

    seq = x.shape[1]
    use_fused = getattr(cfg, "use_fused_attention", "auto")
    if use_fused == "auto":
        # measured on v5e: at S=128 the XLA einsum-GEMM path wins — the
        # fused per-head kernel drowns in layout glue (126 ms step vs
        # 86) and the packed kernel in per-chunk latency (157 ms); from
        # S>=256 the in-VMEM fusion pays for itself
        use_fused = seq >= 256
    if use_fused == "packed":
        # q/k/v stay in the fc-native [B, S, H*d] layout end to end
        ctx = layers.fused_attention_packed(
            q, k, v, n_heads, attn_bias,
            dropout_prob=cfg.attn_dropout or 0.0)
    elif use_fused:
        # one pallas kernel per (batch-block, head): scores/softmax/
        # dropout/PV stay in VMEM (jnp fallback off-TPU) —
        # paddle_tpu/kernels/attention.py
        def split_heads(t):
            t = layers.reshape(t, [0, 0, n_heads, d])
            return layers.transpose(t, [0, 2, 1, 3])  # [B, nH, S, d]

        ctx = layers.fused_attention(
            split_heads(q), split_heads(k), split_heads(v), attn_bias,
            dropout_prob=cfg.attn_dropout or 0.0)
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h])
    else:
        # einsum straight from the fc-native [B, S, H, d] layout: XLA
        # folds the head split into the GEMMs instead of materializing
        # [B, H, S, d] transposes (188k -> 191k tok/s at base config)
        q4 = layers.reshape(q, [0, 0, n_heads, d])
        k4 = layers.reshape(k, [0, 0, n_heads, d])
        v4 = layers.reshape(v, [0, 0, n_heads, d])
        scores = layers.scale(
            layers.einsum("bqhd,bkhd->bhqk", q4, k4),
            scale=1.0 / math.sqrt(d))
        scores = layers.elementwise_add(scores, attn_bias)
        weights = layers.softmax(scores)
        if cfg.attn_dropout:
            weights = layers.dropout(
                weights, cfg.attn_dropout,
                dropout_implementation="upscale_in_train")
        ctx = layers.reshape(
            layers.einsum("bhqk,bkhd->bqhd", weights, v4), [0, 0, h])
    return layers.fc(ctx, h, num_flatten_dims=2, name=prefix + "_out",
                     param_attr=_tp_attr(cfg, "row"))


def _encoder_layer(x, attn_bias, cfg, prefix):
    attn = _mha(x, attn_bias, cfg, prefix + "_attn")
    if cfg.hidden_dropout:
        attn = layers.dropout(attn, cfg.hidden_dropout,
                              dropout_implementation="upscale_in_train")
    x = layers.layer_norm(layers.elementwise_add(x, attn), begin_norm_axis=2)
    ffn = layers.fc(x, cfg.ffn_hidden, num_flatten_dims=2, act="gelu",
                    name=prefix + "_ffn1",
                    param_attr=_tp_attr(cfg, "col"))
    ffn = layers.fc(ffn, cfg.hidden, num_flatten_dims=2,
                    name=prefix + "_ffn2",
                    param_attr=_tp_attr(cfg, "row"))
    if cfg.hidden_dropout:
        ffn = layers.dropout(ffn, cfg.hidden_dropout,
                             dropout_implementation="upscale_in_train")
    return layers.layer_norm(layers.elementwise_add(x, ffn), begin_norm_axis=2)


def bert_encoder(src_ids, pos_ids, sent_ids, input_mask, cfg):
    """input_mask: [B, S, 1] float (1 = token, 0 = pad). Returns [B, S, H]."""
    assert src_ids.shape[-1] <= cfg.max_seq, (
        f"seq_len {src_ids.shape[-1]} exceeds cfg.max_seq {cfg.max_seq}: "
        "positions past max_seq would silently clamp in the pos-emb gather")
    emb = layers.embedding(src_ids, size=[cfg.vocab_size, cfg.hidden],
                           param_attr=fluid.ParamAttr(name="word_emb"))
    emb = layers.elementwise_add(
        emb, layers.embedding(pos_ids, size=[cfg.max_seq, cfg.hidden],
                              param_attr=fluid.ParamAttr(name="pos_emb")))
    emb = layers.elementwise_add(
        emb, layers.embedding(sent_ids, size=[cfg.type_vocab, cfg.hidden],
                              param_attr=fluid.ParamAttr(name="sent_emb")))
    x = layers.layer_norm(emb, begin_norm_axis=2)
    if cfg.hidden_dropout:
        x = layers.dropout(x, cfg.hidden_dropout,
                           dropout_implementation="upscale_in_train")

    # additive attention bias [B, 1, 1, S]: 0 keep, -1e4 mask
    mask = layers.transpose(input_mask, [0, 2, 1])  # [B, 1, S]
    bias = layers.scale(mask, scale=1e4, bias=-1e4)
    attn_bias = layers.unsqueeze(bias, axes=[1])

    for i in range(cfg.n_layers):
        x = _encoder_layer(x, attn_bias, cfg, "layer_%d" % i)
    return x


def _mlm_logits(x2d, cfg):
    """Vocab projection for the MLM head. By default the decoder weight is
    TIED to the word embedding table (the reference's ``weight_sharing``,
    dist_transformer.py:159,1466: output projection = matmul against the
    embedding param, transpose_y) — halves the vocab-sized parameter/
    optimizer-state footprint. ``cfg.tie_mlm_decoder=False`` restores an
    untied fc."""
    if getattr(cfg, "tie_mlm_decoder", True):
        name = getattr(cfg, "embedding_param_name", "word_emb")
        try:
            table = fluid.default_main_program().global_block().var(name)
        except Exception:
            # head built without bert_encoder in this program (custom
            # encoder / renamed table): fall back to an untied decoder
            table = None
        if table is not None:
            logits = layers.matmul(x2d, table, transpose_y=True)
            bias = layers.create_parameter(
                [cfg.vocab_size], "float32", name="mlm_out_bias",
                default_initializer=fluid.initializer.Constant(0.0))
            return layers.elementwise_add(logits, bias)
    return layers.fc(x2d, cfg.vocab_size, name="mlm_logits")


def mlm_loss(enc, mask_label, mask_weight, cfg):
    """Masked-LM loss over all positions, weighted by mask_weight
    [B, S, 1] (1 on masked positions). Static shapes: no gather of dynamic
    position counts — the weighting keeps XLA shapes fixed."""
    x = layers.fc(enc, cfg.hidden, num_flatten_dims=2, act="gelu",
                  name="mlm_transform")
    x = layers.layer_norm(x, begin_norm_axis=2)
    b, s = enc.shape[0], enc.shape[1]
    logits = layers.reshape(
        _mlm_logits(layers.reshape(x, [-1, cfg.hidden]), cfg),
        [b, s, cfg.vocab_size])
    ce = layers.softmax_with_cross_entropy(logits, mask_label)  # [B, S, 1]
    num = layers.reduce_sum(layers.elementwise_mul(ce, mask_weight))
    den = layers.reduce_sum(mask_weight)
    return layers.elementwise_div(
        num, layers.elementwise_add(den, layers.fill_constant([1], "float32",
                                                              1e-6)))


def mlm_loss_masked(enc, mask_pos, mask_label, mask_weight, cfg):
    """Masked-LM loss over GATHERED masked positions only — the
    reference's ERNIE head (``mask_pos`` flat indices into [B*S, H]).
    The vocab projection runs on B*P rows instead of B*S (P = max
    predictions/seq ≈ 0.15*S), cutting the head matmul and the [.., V]
    logit HBM traffic ~6x; padding slots carry weight 0."""
    h = cfg.hidden
    flat = layers.reshape(enc, [-1, h])                  # [B*S, H]
    sel = layers.gather(flat, layers.reshape(mask_pos, [-1]))  # [B*P, H]
    x = layers.fc(sel, h, act="gelu", name="mlm_transform")
    x = layers.layer_norm(x, begin_norm_axis=1)
    logits = _mlm_logits(x, cfg)
    ce = layers.softmax_with_cross_entropy(
        logits, layers.reshape(mask_label, [-1, 1]))     # [B*P, 1]
    w = layers.reshape(mask_weight, [-1, 1])
    num = layers.reduce_sum(layers.elementwise_mul(ce, w))
    den = layers.reduce_sum(w)
    return layers.elementwise_div(
        num, layers.elementwise_add(den, layers.fill_constant([1], "float32",
                                                              1e-6)))


def max_predictions(seq_len):
    """Standard BERT budget: 15% of positions, at least 1."""
    return max(1, int(seq_len * 0.15))


def build_pretrain_program(cfg=None, seq_len=128, lr=1e-4, seed=7,
                           use_amp=False, masked_gather=True):
    cfg = cfg or BertConfig.base()
    n_pred = max_predictions(seq_len)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        src = layers.data("src_ids", shape=[seq_len], dtype="int64")
        pos = layers.data("pos_ids", shape=[seq_len], dtype="int64")
        sent = layers.data("sent_ids", shape=[seq_len], dtype="int64")
        imask = layers.data("input_mask", shape=[seq_len, 1], dtype="float32")
        enc = bert_encoder(src, pos, sent, imask, cfg)
        if masked_gather:
            mpos = layers.data("mask_pos", shape=[n_pred], dtype="int64")
            mlabel = layers.data("mask_label", shape=[n_pred],
                                 dtype="int64")
            mweight = layers.data("mask_weight", shape=[n_pred],
                                  dtype="float32")
            loss = mlm_loss_masked(enc, mpos, mlabel, mweight, cfg)
        else:
            mlabel = layers.data("mask_label", shape=[seq_len, 1],
                                 dtype="int64")
            mweight = layers.data("mask_weight", shape=[seq_len, 1],
                                  dtype="float32")
            loss = mlm_loss(enc, mlabel, mweight, cfg)
        opt = optimizer.Adam(learning_rate=lr)
        if use_amp:
            from ..fluid.contrib import mixed_precision

            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss


def build_encoder_program(cfg=None, seq_len=128, seed=7):
    """Inference-mode encoder: dropout disabled so the forward is
    deterministic (the graft-entry / predictor surface)."""
    import copy

    cfg = copy.copy(cfg or BertConfig.base())
    cfg.hidden_dropout = 0.0
    cfg.attn_dropout = 0.0
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        src = layers.data("src_ids", shape=[seq_len], dtype="int64")
        pos = layers.data("pos_ids", shape=[seq_len], dtype="int64")
        sent = layers.data("sent_ids", shape=[seq_len], dtype="int64")
        imask = layers.data("input_mask", shape=[seq_len, 1], dtype="float32")
        enc = bert_encoder(src, pos, sent, imask, cfg)
    return main, startup, enc


def synthetic_batch(cfg, batch, seq_len, seed=0, masked_gather=True):
    import numpy as np

    rng = np.random.RandomState(seed)
    src = rng.randint(0, cfg.vocab_size, (batch, seq_len)).astype("int64")
    pos = np.tile(np.arange(seq_len, dtype="int64"), (batch, 1))
    sent = np.zeros((batch, seq_len), "int64")
    imask = np.ones((batch, seq_len, 1), "float32")
    feed = {"src_ids": src, "pos_ids": pos, "sent_ids": sent,
            "input_mask": imask}
    if masked_gather:
        n_pred = max_predictions(seq_len)
        # flat indices into [B*S]: row b picks n_pred distinct positions
        local = np.stack([rng.choice(seq_len, n_pred, replace=False)
                          for _ in range(batch)])
        feed["mask_pos"] = (local +
                            np.arange(batch)[:, None] * seq_len).astype(
                                "int64")
        feed["mask_label"] = rng.randint(
            0, cfg.vocab_size, (batch, n_pred)).astype("int64")
        feed["mask_weight"] = np.ones((batch, n_pred), "float32")
    else:
        feed["mask_label"] = rng.randint(
            0, cfg.vocab_size, (batch, seq_len, 1)).astype("int64")
        feed["mask_weight"] = (rng.rand(batch, seq_len, 1) <
                               0.15).astype("float32")
    return feed
