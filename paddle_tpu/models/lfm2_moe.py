"""LFM2-MoE (LiquidAI's ``lfm2_moe``): a decoder-only causal LM whose token
mixers are gated short convolutions in most layers and grouped-query
softmax attention in the rest, by ``layer_types``; the leading
``num_dense_layers`` layers' feed-forward part is a dense SwiGLU, every
other layer's a sigmoid-routed sparse expert layer with no shared expert.
Built from ``fluid.layers`` only; a training step is
``fluid.Executor().run(main, feed, fetch_list=[loss])`` with feeds
``tokens`` and ``labels`` [B, S].

Layer ``l`` counted from 0 (``h`` the hidden size, ``rms`` with a plain
weight at ``norm_eps``; no bias anywhere)::

    u = x + mixer_l(rms(x; w1));  y = u + ffn_l(rms(u; w2))
    mixer_l = conv or full_attention, by layer_types[l];
    ffn_l = dense SwiGLU (intermediate_size) for l < num_dense_layers,
            else the expert layer

    conv  [B | C | x] = W_in h (3h wide, in that channel order);
          out = W_out (C * conv_K(B * x)), the depthwise causal filter of
          K = conv_L_cache taps, no bias, no activation
          (``layers.gated_short_conv``: one op on the unsplit projection)
    attn  q = W_q h (H heads of d = h / H), k, v = W_k h, W_v h (Hkv
          heads); q and k RMS-normed a head over their d channels
          (q_layernorm, k_layernorm), then the rotary embedding over all d
          channels at rope_parameters["rope_theta"]; causal softmax
          attention at scale d^-0.5; out = W_o o
    MoE   ``decoder_blocks.routed_experts``: sigmoid scores over all
          experts, top-k of score + a selection-only bias (use_expert_bias;
          frozen, zero here), the chosen scores renormalised
          (norm_topk_prob) times routed_scaling_factor; SwiGLU experts

then a final RMS norm (the released code's ``embedding_norm``) before the
head. The expert layer is one expert-parallel rank's share: the router
scores all ``num_experts_total`` experts; this rank holds ``num_experts``
of them from ``expert_offset`` on. Parameter names are fixed
(``layer_1_attn_q_w``; layers named from 0), so a reference can find its
leaves.
"""

from paddle_tpu.fluid import layers

from . import decoder_blocks
from .decoder_blocks import attr as _attr
from .decoder_blocks import proj as _proj


class Lfm2MoeConfig(decoder_blocks.DecoderConfig):
    """The keys of the model's ``config.json`` that shape a step, under
    their published names. ``num_experts`` counts the experts HELD here;
    ``num_experts_total`` is the router's width."""

    def __init__(self, **kw):
        self.vocab_size = 65536
        self.hidden_size = 2048
        self.num_hidden_layers = 40
        self.layer_types = (["conv", "conv", "full_attention"]
                            + ["conv", "conv", "conv", "full_attention"] * 9
                            + ["conv"])
        self.num_dense_layers = 2
        self.conv_L_cache = 3
        self.conv_bias = False
        self.num_attention_heads = 32
        self.num_key_value_heads = 8
        self.rope_parameters = {"rope_theta": 1000000, "rope_type": "default"}
        self.norm_eps = 1e-5
        self.intermediate_size = 11776
        self.moe_intermediate_size = 1536
        self.num_experts = 64
        self.num_experts_total = 64
        self.expert_offset = 0
        self.num_experts_per_tok = 4
        self.use_expert_bias = True
        self.norm_topk_prob = True
        self.routed_scaling_factor = 1.0
        self.initializer_range = 0.02
        self._override(kw)
        assert len(self.layer_types) == self.num_hidden_layers, \
            (len(self.layer_types), self.num_hidden_layers)
        assert set(self.layer_types) <= {"conv", "full_attention"}, \
            self.layer_types
        # the program's router always carries the selection-only bias, and
        # the filter has none
        assert self.use_expert_bias and not self.conv_bias
        assert self.rope_parameters.get("rope_type", "default") == "default"

    # what ``decoder_blocks`` reads, under the names the other decoders'
    # configs publish
    rms_norm_eps = property(lambda self: self.norm_eps)
    moe_router_activation_func = "sigmoid"
    head_dim = property(
        lambda self: self.hidden_size // self.num_attention_heads)

    def is_full_attention(self, i):
        """Layer ``i`` counted from 0."""
        return self.layer_types[i] == "full_attention"


def _rms(x, name, cfg):
    return decoder_blocks.rms(x, name, cfg, zero_centered=False)


def _conv(x, cfg, p):
    y = layers.gated_short_conv(
        _proj(x, 3 * cfg.hidden_size, p + "_in", cfg), cfg.conv_L_cache,
        param_attr=_attr(p + "_filter", cfg), name=p + "_core")
    return _proj(y, cfg.hidden_size, p + "_out", cfg)


def _attention(x, cfg, p):
    H, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    theta = cfg.rope_parameters["rope_theta"]

    def heads(size, name):      # [B, heads, S, d], the q and k ones normed
        t = layers.reshape(_proj(x, size * d, p + "_" + name, cfg),
                           [0, 0, size, d])
        if name != "v":
            t = _rms(t, p + "_" + name + "_norm", cfg)
        t = layers.transpose(t, [0, 2, 1, 3])
        if name != "v":
            t = layers.rotary_embedding(t, d, theta,
                                        name=p + "_" + name + "_rope")
        return t

    ctx = layers.fused_attention(heads(H, "q"), heads(Hkv, "k"),
                                 heads(Hkv, "v"), scale=d ** -0.5,
                                 causal=True, num_kv_heads=Hkv,
                                 name=p + "_core")
    return _proj(layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                                [0, 0, H * d]), cfg.hidden_size, p + "_o",
                 cfg)


def decoder(tokens, cfg):
    """``tokens`` [B, S] int64 -> (hidden states after the final norm
    [B, S, h], the residual stream after each layer)."""
    x = layers.embedding(
        layers.unsqueeze(tokens, [2]), [cfg.vocab_size, cfg.hidden_size],
        param_attr=_attr("embed_tokens", cfg))
    boundaries = []
    for i in range(cfg.num_hidden_layers):
        p = "layer_%d" % i
        h = _rms(x, p + "_norm1", cfg)
        x = layers.elementwise_add(
            x, _attention(h, cfg, p + "_attn") if cfg.is_full_attention(i)
            else _conv(h, cfg, p + "_conv"), name=p + "_res1")
        h = _rms(x, p + "_norm2", cfg)
        x = layers.elementwise_add(
            x, decoder_blocks.mlp(h, cfg.intermediate_size, cfg, p + "_mlp")
            if i < cfg.num_dense_layers
            else decoder_blocks.routed_experts(h, cfg, p + "_moe"),
            name=p + "_res2")
        boundaries.append(x)
    return _rms(x, "final_norm", cfg), boundaries


def build_train_program(cfg, batch, seq_len, lr=1e-4, use_amp=True,
                        recompute=False, seed=7):
    """``decoder_blocks.build_train_program`` round ``decoder``."""
    return decoder_blocks.build_train_program(
        decoder, cfg, batch, seq_len, lr=lr, use_amp=use_amp,
        recompute=recompute, seed=seed)
