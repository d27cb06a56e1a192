"""Kimi Linear: a decoder-only causal LM whose token mixers are Kimi Delta
Attention layers (KDA: a delta rule whose state decays by a gate a key
CHANNEL) three times in four and latent attention without positions (MLA,
NoPE) the fourth; the first layer's feed-forward is a dense MLP, every
other layer's a sigmoid-routed sparse expert layer with a shared expert.
Built from ``fluid.layers`` only; a training step is
``fluid.Executor().run(main, feed, fetch_list=[loss])`` with feeds
``tokens`` and ``labels`` [B, S]. No layer takes a position.

Layer ``l`` counted from 1 (``h`` the hidden size, ``rms`` with a plain
weight; no bias anywhere)::

    u = x + mixer_l(rms(x; w1));  y = u + ffn_l(rms(u; w2))
    mixer_l = MLA where l is in linear_attn_config["full_attn_layers"],
              else KDA;  ffn_l = dense MLP for l <= first_k_dense_replace,
              else the expert layer

    KDA  q, k, v = silu(conv4(W x)) each with its own projection and its
         own 4-tap causal depthwise filters (one fused projection and one
         fused convolution here, columns [q | k | v], heads in order);
         q, k L2-normalised a head (q times dk^-0.5) inside the op;
         g = -exp(A_log[head]) * softplus(Wfb (Wfa x) + dt_bias) a channel;
         beta = sigmoid(Wb x);  the channel-gated delta rule
         (``layers.gated_delta_rule`` with a 4-D gate);
         out = Wo (rms_head(o; w_o) * sigmoid(Wgb (Wga x)))
    MLA  q = Wq x [H x (nope + rope)];  [c | k_s] = Wkva x (kv_lora_rank |
         rope); [k_n | v] = Wkvb rms(c; w_c) a head; a head's key is [k_n |
         k_s] with k_s shared by all heads; NO rotation (mla_use_nope);
         softmax((q . k) (nope + rope)^-0.5) v over s <= t, v 128 wide
         against 192-wide q and k (``layers.fused_attention`` at two
         widths); out = Wo o
    MoE  ``decoder_blocks.routed_experts`` (sigmoid scores, a selection-
         only bias, frozen and zero, ``routed_scaling_factor``) plus the
         shared expert, which is whole

The expert layer is one expert-parallel rank's share: the router scores
all ``num_experts_total`` experts; this rank holds ``num_experts`` of them
from ``expert_offset`` on. Parameter names are fixed (``layer_3_mla_q_w``;
layers named from 0), so a reference can find its leaves.
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

from . import decoder_blocks
from .decoder_blocks import attr as _attr
from .decoder_blocks import proj as _proj


class KimiLinearConfig(decoder_blocks.DecoderConfig):
    """The keys of the model's ``config.json`` that shape a step, under
    their published names (``linear_attn_config`` as the nested group it
    is; its layer lists count from 1). ``num_experts`` counts the experts
    HELD here; ``num_experts_total`` is the router's width."""

    def __init__(self, **kw):
        self.vocab_size = 163840
        self.hidden_size = 2304
        self.num_hidden_layers = 27
        self.linear_attn_config = {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4}
        self.num_attention_heads = 32
        self.kv_lora_rank = 512
        self.qk_nope_head_dim = 128
        self.qk_rope_head_dim = 64
        self.v_head_dim = 128
        self.rms_norm_eps = 1e-5
        self.first_k_dense_replace = 1
        self.intermediate_size = 9216
        self.num_experts = 256
        self.num_experts_total = 256
        self.expert_offset = 0
        self.num_experts_per_token = 8
        self.num_shared_experts = 1
        self.moe_intermediate_size = 1024
        self.moe_renormalize = True
        self.moe_router_activation_func = "sigmoid"
        self.routed_scaling_factor = 2.446
        self.initializer_range = 0.02
        self.kda_chunk_size = 64
        self._override(kw)
        lin = self.linear_attn_config
        assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(
            range(1, self.num_hidden_layers + 1)), lin
        assert self.moe_router_activation_func == "sigmoid"

    # what ``decoder_blocks.routed_experts`` reads, under the names the
    # other decoders' configs publish
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    norm_topk_prob = property(lambda self: self.moe_renormalize)

    def is_full_attention(self, i):
        """Layer ``i`` counted from 0."""
        return i + 1 in self.linear_attn_config["full_attn_layers"]


def _rms(x, name, cfg):
    return decoder_blocks.rms(x, name, cfg, zero_centered=False)


def _low_rank(x, rank, size, p, cfg):
    return _proj(_proj(x, rank, p + "_a", cfg), size, p + "_b", cfg)


def _kda(x, cfg, p):
    lin = cfg.linear_attn_config
    H, d, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    qkv = layers.causal_conv1d(
        _proj(x, 3 * H * d, p + "_qkv", cfg), K,
        param_attr=_attr(p + "_conv_w", cfg), act="swish", name=p + "_conv")
    q, k, v = (layers.reshape(t, [0, 0, H, d]) for t in
               layers.split(qkv, 3, dim=-1, name=p + "_split"))
    o = layers.gated_delta_rule(
        q, k, v, layers.reshape(_low_rank(x, d, H * d, p + "_f", cfg),
                                [0, 0, H, d]),
        _proj(x, H, p + "_b", cfg),
        a_log_attr=fluid.ParamAttr(name=p + "_a_log"),
        dt_bias_attr=fluid.ParamAttr(name=p + "_dt_bias"),
        chunk_size=cfg.kda_chunk_size, name=p + "_rule")
    gate = layers.reshape(_low_rank(x, d, H * d, p + "_g", cfg), [0, 0, H, d])
    o = layers.elementwise_mul(_rms(o, p + "_norm", cfg),
                               layers.sigmoid(gate), name=p + "_gate")
    return _proj(layers.reshape(o, [0, 0, H * d]), cfg.hidden_size,
                 p + "_o", cfg)


def _mla(x, cfg, p):
    H = cfg.num_attention_heads
    dn, ds, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def heads_first(t):
        return layers.transpose(t, [0, 2, 1, 3])        # [B, H, S, d]

    q = heads_first(layers.reshape(
        _proj(x, H * (dn + ds), p + "_q", cfg), [0, 0, H, dn + ds]))
    c, k_s = layers.split(_proj(x, cfg.kv_lora_rank + ds, p + "_kv_a", cfg),
                          [cfg.kv_lora_rank, ds], dim=-1,
                          name=p + "_kv_a_split")
    kv = layers.reshape(
        _proj(_rms(c, p + "_kv_norm", cfg), H * (dn + dv), p + "_kv_b", cfg),
        [0, 0, H, dn + dv])
    k_n, v = layers.split(kv, [dn, dv], dim=-1, name=p + "_kv_b_split")
    # one 64-wide key part for all heads, no rotation on any part
    k_s = layers.expand(layers.unsqueeze(k_s, [1]), [1, H, 1, 1])
    k = layers.concat([heads_first(k_n), k_s], axis=3)
    ctx = layers.fused_attention(q, k, heads_first(v),
                                 scale=(dn + ds) ** -0.5, causal=True,
                                 name=p + "_core")
    return _proj(layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                                [0, 0, H * dv]), cfg.hidden_size, p + "_o",
                 cfg)


def _mlp(x, width, cfg, p):
    return _proj(layers.swiglu(_proj(x, width, p + "_gate", cfg),
                               _proj(x, width, p + "_up", cfg),
                               name=p + "_act"),
                 cfg.hidden_size, p + "_down", cfg)


def _moe(x, cfg, p):
    return layers.elementwise_add(
        decoder_blocks.routed_experts(x, cfg, p),
        _mlp(x, cfg.moe_intermediate_size * cfg.num_shared_experts, cfg,
             p + "_shared"), name=p + "_sum")


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
            x, _mla(h, cfg, p + "_mla") if cfg.is_full_attention(i)
            else _kda(h, cfg, p + "_kda"), name=p + "_res1")
        h = _rms(x, p + "_norm2", cfg)
        x = layers.elementwise_add(
            x, _mlp(h, cfg.intermediate_size, cfg, p + "_mlp")
            if i < cfg.first_k_dense_replace else _moe(h, cfg, p + "_moe"),
            name=p + "_res2")
        boundaries.append(x)
    return _rms(x, "final_norm", cfg), boundaries


def build_train_program(cfg, batch, seq_len, lr=1e-4, use_amp=True,
                        recompute=False, seed=7):
    """``decoder_blocks.build_train_program`` round ``decoder``."""
    return decoder_blocks.build_train_program(
        decoder, cfg, batch, seq_len, lr=lr, use_amp=use_amp,
        recompute=recompute, seed=seed)
