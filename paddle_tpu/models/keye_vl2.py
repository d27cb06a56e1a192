"""Keye-VL-2.0's language model: a decoder-only causal LM whose every layer
is grouped-query softmax attention under a LEARNED selection of keys (a
lightning indexer, as DeepSeek-V3.2-Exp's sparse attention) followed by a
sparse expert layer with no shared expert. Built from ``fluid.layers``
only; a training step is ``fluid.Executor().run(main, feed,
fetch_list=[loss])`` with feeds ``tokens``, ``labels`` [B, S] and
``positions`` [3, B, S] (time, height, width; text: three equal rows).

Layer ``i`` (``h`` the hidden size, ``rms`` with a plain weight)::

    u = x + attn(rms(x; w1));  y = u + moe(rms(u; w2))

    attn:  q, k = rms per head of the projections, then the rotary
           embedding in ``mrope_section``'s three sections; v as projected
           indexer: qI [Hi x di], kI = layer_norm of ONE shared key head
           [di], wI [Hi] from the same x; rotary (the first row's
           positions) on qI, kI;  I[t, s] = sum_j wI[t, j] relu(qI[t, j] .
           kI[s]);  S_t = the min(t + 1, topk) keys s <= t of largest I
           softmax attention of every head over S_t only, then Wo

The selection carries no gradient, so under the next-token loss the
indexer's parameters receive exactly zero: they are built
``trainable=False`` (no gradient, no Adam state), and the forward uses
them. The vision tower is not here; what it asks of the language model
(positions in three rows) is. The expert layer is one expert-parallel
rank's share (``decoder_blocks.routed_experts``). Parameter names are
fixed (``layer_3_attn_q_w``), so a reference can find its leaves.
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

from . import decoder_blocks
from .decoder_blocks import proj as _proj
from .decoder_blocks import rms as _rms


class KeyeVL2Config(decoder_blocks.DecoderConfig):
    """The keys of the model's ``config.json`` that shape a step, under
    their published names (``sa_config`` and ``rope_scaling`` as the nested
    groups they are). ``num_experts`` counts the experts HELD here;
    ``num_experts_total`` is the router's width. ``embedding_std``: the
    startup program's draw of ``embed_tokens`` where it is not
    ``initializer_range`` (None: it is)."""

    def __init__(self, **kw):
        self.vocab_size = 151936
        self.hidden_size = 2048
        self.num_hidden_layers = 48
        self.num_attention_heads = 32
        self.num_key_value_heads = 4
        self.head_dim = 128
        self.rope_theta = 10000000.0
        self.rope_scaling = {"mrope_section": [16, 24, 24]}
        self.rms_norm_eps = 1e-6
        self.sa_config = {"indexer_head_dim": 64, "indexer_num_heads": 16,
                          "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                          "q_chunk_size": 512, "topk": 2048}
        self.num_experts = 128
        self.num_experts_total = 128
        self.expert_offset = 0
        self.num_experts_per_tok = 8
        self.norm_topk_prob = True
        self.moe_intermediate_size = 768
        self.initializer_range = 0.02
        self.embedding_std = None
        self._override(kw)
        assert self.sa_config["indexer_num_kv_heads"] == 1, self.sa_config


def _heads_first(t):
    return layers.transpose(t, [0, 2, 1, 3])            # [B, H, S, d]


def _indexer(x, cfg, p, row_positions):
    """The selection [B, S, S] of layer ``p`` from its input ``x``."""
    sa = cfg.sa_config
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]

    def frozen(name):
        return fluid.ParamAttr(name=name, trainable=False)

    def rope(t, name):
        return layers.rotary_embedding(t, di, cfg.rope_theta,
                                       positions=row_positions, name=name)

    q = layers.reshape(_proj(x, Hi * di, p + "_q", cfg, trainable=False),
                       [0, 0, Hi, di])
    k = layers.layer_norm(
        _proj(x, di, p + "_k", cfg, trainable=False), begin_norm_axis=2,
        epsilon=cfg.rms_norm_eps, param_attr=frozen(p + "_k_norm_w"),
        bias_attr=frozen(p + "_k_norm_b"), name=p + "_k_norm")
    w = _proj(x, Hi, p + "_weights", cfg, trainable=False)
    q = rope(_heads_first(q), p + "_q_rope")            # [B, Hi, S, di]
    k = layers.squeeze(rope(layers.unsqueeze(k, [1]), p + "_k_rope"), [1])
    return layers.sparse_index(q, k, w, sa["topk"],
                               chunk_size=sa["q_chunk_size"],
                               name=p + "_select")


def _attention(x, cfg, p, positions, row_positions):
    H, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    sections = cfg.rope_scaling["mrope_section"]

    def rope(t, name):
        return layers.rotary_embedding(
            _heads_first(t), d, cfg.rope_theta, positions=positions,
            mrope_section=sections, name=name)

    q = layers.reshape(_proj(x, H * d, p + "_q", cfg), [0, 0, H, d])
    k = layers.reshape(_proj(x, Hkv * d, p + "_k", cfg), [0, 0, Hkv, d])
    v = layers.reshape(_proj(x, Hkv * d, p + "_v", cfg), [0, 0, Hkv, d])
    q = rope(_rms(q, p + "_q_norm", cfg, zero_centered=False), p + "_q_rope")
    k = rope(_rms(k, p + "_k_norm", cfg, zero_centered=False), p + "_k_rope")
    select = _indexer(x, cfg, p + "_idx", row_positions)
    ctx = layers.fused_attention(q, k, _heads_first(v), scale=d ** -0.5,
                                 causal=True, num_kv_heads=Hkv,
                                 select=select, name=p + "_core")
    return _proj(layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                                [0, 0, H * d]), cfg.hidden_size, p + "_o", cfg)


def decoder(tokens, cfg, positions=None):
    """``tokens`` [B, S] int64, ``positions`` [3, B, S] int64 (made as the
    feed ``positions`` where None) -> (hidden states after the final norm
    [B, S, h], the residual stream after each layer)."""
    if positions is None:
        positions = layers.data(
            "positions", shape=[3] + [int(n) for n in tokens.shape],
            dtype="int64", append_batch_size=False)
    # the indexer's one-section rotary embedding turns by the first row
    row_positions = layers.squeeze(
        layers.slice(positions, axes=[0], starts=[0], ends=[1]), [0])
    x = layers.embedding(
        layers.unsqueeze(tokens, [2]), [cfg.vocab_size, cfg.hidden_size],
        param_attr=fluid.ParamAttr(
            name="embed_tokens", initializer=fluid.initializer.Normal(
                0.0, cfg.embedding_std or cfg.initializer_range)))
    boundaries = []
    for i in range(cfg.num_hidden_layers):
        p = "layer_%d" % i
        x = layers.elementwise_add(
            x, _attention(_rms(x, p + "_norm1", cfg, zero_centered=False),
                          cfg, p + "_attn", positions, row_positions),
            name=p + "_res1")
        x = layers.elementwise_add(
            x, decoder_blocks.routed_experts(
                _rms(x, p + "_norm2", cfg, zero_centered=False), cfg,
                p + "_moe"),
            name=p + "_res2")
        boundaries.append(x)
    return _rms(x, "final_norm", cfg, zero_centered=False), boundaries


def build_train_program(cfg, batch, seq_len, lr=1e-4, use_amp=True,
                        recompute=False, seed=7):
    """``decoder_blocks.build_train_program`` round ``decoder``: feeds
    ``tokens``, ``labels`` [batch, seq_len] and ``positions`` [3, batch,
    seq_len]."""
    return decoder_blocks.build_train_program(
        decoder, cfg, batch, seq_len, lr=lr, use_amp=use_amp,
        recompute=recompute, seed=seed)
