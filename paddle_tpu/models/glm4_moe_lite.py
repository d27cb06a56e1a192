"""GLM-4.7-Flash (zai-org's ``glm4_moe_lite``): a decoder-only causal LM
whose every token mixer is multi-head latent attention with a low-rank
query and a rotary key part shared by all heads; the leading
``first_k_dense_replace`` layers' feed-forward part is a dense SwiGLU,
every other layer's a sigmoid-routed expert layer beside a shared expert;
and one multi-token-prediction (MTP) module whose loss is a second term.
Built from ``fluid.layers`` only; a training step is
``fluid.Executor().run(main, feed, fetch_list=[loss])`` with feeds
``tokens`` and ``labels`` [B, S] (``labels`` the next ids).

Layer ``l`` counted from 0 (``h`` the hidden size, ``rms`` with a plain
weight at ``rms_norm_eps``; no bias anywhere)::

    u = x + mla(rms(x; w1));  y = u + ffn_l(rms(u; w2))
    ffn_l = dense SwiGLU (intermediate_size) for l < first_k_dense_replace,
            else the expert layer

    mla  ``decoder_blocks.latent_attention``: q = W_qb rms(W_qa h)
         (q_lora_rank), a head's [q_n (192) | q_r (64)]; [c (512) | k_r
         (64)] = W_kva h; [k_n (192) | v (256)] = W_kvb rms(c) a head;
         q_r and the ONE k_r all heads share rotated (rotate-half over all
         64 channels at rope_theta, positions 0..S-1); causal softmax at
         (192 + 64)^-0.5; out = W_o o
    MoE  ``decoder_blocks.routed_experts``: sigmoid scores over all
         experts in f32, top-k of score + a selection-only bias (noaux_tc;
         frozen, zero here), the chosen scores renormalised
         (norm_topk_prob) times routed_scaling_factor; SwiGLU experts of
         moe_intermediate_size; plus the shared expert(s), whole

then a final RMS norm and the head ``lm_head`` (untied). The MTP module
(``num_nextn_predict_layers`` 1; ``decoder_blocks.multi_token_prediction``)
is one more expert layer over ``W_eh [rms(Emb(t_{i+1})) ; rms(h_i)]``,
read by the same head to predict ``t_{i+2}``; the loss is ``L_main +
mtp_loss_factor * L_mtp``.

The expert layer is one expert-parallel rank's share: the router scores
all ``num_experts_total`` experts; this rank holds ``n_routed_experts`` of
them from ``expert_offset`` on. Parameter names are fixed
(``layer_3_mla_q_b_w``, ``mtp_0_eh_proj_w``; layers named from 0), so a
reference can find its leaves.
"""

from paddle_tpu.fluid import layers

from . import decoder_blocks


class Glm4MoeLiteConfig(decoder_blocks.DecoderConfig):
    """The keys of the model's ``config.json`` that shape a step, under
    their published names. ``n_routed_experts`` counts the experts HELD
    here; ``num_experts_total`` is the router's width. ``mtp_loss_factor``
    is no published key: the MTP loss's weight in the sum."""

    def __init__(self, **kw):
        self.vocab_size = 154880
        self.hidden_size = 2048
        self.num_hidden_layers = 47
        self.num_attention_heads = 20
        self.num_key_value_heads = 20
        self.q_lora_rank = 768
        self.kv_lora_rank = 512
        self.qk_nope_head_dim = 192
        self.qk_rope_head_dim = 64
        self.v_head_dim = 256
        self.rope_theta = 1000000
        self.rope_scaling = None
        self.partial_rotary_factor = 1
        self.attention_bias = False
        self.rms_norm_eps = 1e-5
        self.hidden_act = "silu"
        self.first_k_dense_replace = 1
        self.intermediate_size = 10240
        self.moe_intermediate_size = 1536
        self.n_routed_experts = 64
        self.num_experts_total = 64
        self.expert_offset = 0
        self.n_shared_experts = 1
        self.num_experts_per_tok = 4
        self.topk_method = "noaux_tc"
        self.n_group = 1
        self.topk_group = 1
        self.norm_topk_prob = True
        self.routed_scaling_factor = 1.8
        self.num_nextn_predict_layers = 1
        self.mtp_loss_factor = 0.3
        self.tie_word_embeddings = False
        self.initializer_range = 0.02
        self._override(kw)
        # what the program builds: rotary over the whole rope part, no
        # scaling, no bias, SiLU; the plain top-k (one group); an untied
        # head; at most one MTP module
        assert self.partial_rotary_factor == 1 and self.rope_scaling is None
        assert not self.attention_bias and self.hidden_act == "silu"
        assert self.topk_method == "noaux_tc" and self.n_group == 1 \
            and self.topk_group == 1
        assert self.num_key_value_heads == self.num_attention_heads
        assert not self.tie_word_embeddings
        assert self.num_nextn_predict_layers in (0, 1)

    # what ``decoder_blocks`` reads, under the names the other decoders'
    # configs publish
    num_experts = property(lambda self: self.n_routed_experts)
    num_shared_experts = property(lambda self: self.n_shared_experts)
    moe_router_activation_func = "sigmoid"
    mla_use_nope = False


def _rms(x, name, cfg):
    return decoder_blocks.rms(x, name, cfg, zero_centered=False)


def layer(x, cfg, p, dense=False):
    """One decoder layer under the prefix ``p`` (``layer_3``, ``mtp_0``):
    latent attention, then the dense SwiGLU or the expert layer."""
    h = _rms(x, p + "_norm1", cfg)
    x = layers.elementwise_add(
        x, decoder_blocks.latent_attention(h, cfg, p + "_mla"),
        name=p + "_res1")
    h = _rms(x, p + "_norm2", cfg)
    return layers.elementwise_add(
        x, decoder_blocks.mlp(h, cfg.intermediate_size, cfg, p + "_mlp")
        if dense else decoder_blocks.routed_and_shared_experts(
            h, cfg, p + "_moe"), name=p + "_res2")


def decoder(tokens, cfg):
    """``tokens`` [B, S] int64 -> (hidden states after the final norm
    [B, S, h], the residual stream after each layer)."""
    x = layers.embedding(
        layers.unsqueeze(tokens, [2]), [cfg.vocab_size, cfg.hidden_size],
        param_attr=decoder_blocks.attr("embed_tokens", cfg))
    boundaries = []
    for i in range(cfg.num_hidden_layers):
        x = layer(x, cfg, "layer_%d" % i,
                  dense=i < cfg.first_k_dense_replace)
        boundaries.append(x)
    return _rms(x, "final_norm", cfg), boundaries


def build_train_program(cfg, batch, seq_len, lr=1e-4, use_amp=True,
                        recompute=False, seed=7):
    """``decoder_blocks.build_train_program`` round ``decoder``, with the
    MTP module's loss where ``num_nextn_predict_layers`` is 1."""
    return decoder_blocks.build_train_program(
        decoder, cfg, batch, seq_len, lr=lr, use_amp=use_amp,
        recompute=recompute, seed=seed,
        mtp=layer if cfg.num_nextn_predict_layers else None)
