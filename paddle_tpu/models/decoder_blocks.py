"""What the decoder-only model files here share (``qwen3_next.py``,
``keye_vl2.py``, ``kimi_linear.py``, ``lfm2_moe.py``,
``glm4_moe_lite.py``): a parameter's attribute, the bias-free projection,
the RMS norm, the SwiGLU MLP, latent attention, one expert-parallel
rank's share of a routed expert layer (with shared experts beside it or
without), and the training program round a decoder (next-token
loss, a multi-token-prediction module's loss beside it where the model
has one, Adam, recomputation at the layer boundaries, AMP). A config
``cfg`` is any object with the published key names these read;
``DecoderConfig`` is what the model files' config classes share."""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, optimizer


class DecoderConfig:
    """A model file's config class sets its published keys to the
    published values in ``__init__`` and then calls ``_override(kw)``."""

    def _override(self, kw):
        for k, v in kw.items():
            if not hasattr(self, k):
                raise TypeError("%s has no key %r" % (type(self).__name__, k))
            setattr(self, k, v)

    @classmethod
    def from_dict(cls, d):
        """From a configuration file's dict; keys this class lacks (the
        file's notes, keys that shape no step) are passed over."""
        probe = cls()
        return cls(**{k: v for k, v in d.items() if hasattr(probe, k)})


def attr(name, cfg, trainable=True):
    return fluid.ParamAttr(
        name=name, trainable=trainable,
        initializer=fluid.initializer.Normal(0.0, cfg.initializer_range))


def proj(x, size, name, cfg, trainable=True):
    """A bias-free projection of the last axis of ``x`` [B, S, *]."""
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                     param_attr=attr(name + "_w", cfg, trainable), name=name)


def rms(x, name, cfg, zero_centered=True):
    return layers.rms_norm(x, epsilon=cfg.rms_norm_eps,
                           zero_centered=zero_centered,
                           param_attr=fluid.ParamAttr(name=name), name=name)


def latent_attention(x, cfg, p):
    """Multi-head latent attention (MLA) of ``x`` [B, S, h], causal, with
    plain-weight RMS norms (no bias anywhere; H heads, nope / rope / v the
    ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``)::

        q = W_q x, or W_qb rms(W_qa x) where q_lora_rank is given;
            a head's q is [q_n (nope) | q_r (rope)]
        [c (kv_lora_rank) | k_r (rope)] = W_kva x
        [k_n | v] = W_kvb rms(c), a head's
        a head's key is [k_n | k_r]: ONE k_r for all heads
        q_r and k_r rotated at positions 0..S-1 (rotate-half at
            rope_theta) unless mla_use_nope; k_r once, before it is
            expanded to the heads
        out = W_o softmax((q . k) (nope + rope)^-0.5) v over s <= t

    ``layers.fused_attention`` takes v at its own width. Parameters
    ``<p>_q_w`` (or ``<p>_q_a_w``, ``<p>_q_a_norm``, ``<p>_q_b_w``),
    ``<p>_kv_a_w``, ``<p>_kv_norm``, ``<p>_kv_b_w``, ``<p>_o_w``."""
    H = cfg.num_attention_heads
    dn, ds, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def heads_first(t):
        return layers.transpose(t, [0, 2, 1, 3])        # [B, H, S, d]

    if cfg.q_lora_rank is None:
        q = proj(x, H * (dn + ds), p + "_q", cfg)
    else:
        q = proj(rms(proj(x, cfg.q_lora_rank, p + "_q_a", cfg),
                     p + "_q_a_norm", cfg, zero_centered=False),
                 H * (dn + ds), p + "_q_b", cfg)
    q = heads_first(layers.reshape(q, [0, 0, H, dn + ds]))
    c, k_r = layers.split(proj(x, cfg.kv_lora_rank + ds, p + "_kv_a", cfg),
                          [cfg.kv_lora_rank, ds], dim=-1,
                          name=p + "_kv_a_split")
    kv = layers.reshape(
        proj(rms(c, p + "_kv_norm", cfg, zero_centered=False),
             H * (dn + dv), p + "_kv_b", cfg),
        [0, 0, H, dn + dv])
    k_n, v = layers.split(kv, [dn, dv], dim=-1, name=p + "_kv_b_split")
    k_r = layers.unsqueeze(k_r, [1])                    # [B, 1, S, rope]
    if not cfg.mla_use_nope:
        q_n, q_r = layers.split(q, [dn, ds], dim=-1, name=p + "_q_split")
        q = layers.concat([q_n, layers.rotary_embedding(
            q_r, ds, cfg.rope_theta, name=p + "_q_rope")], axis=3)
        k_r = layers.rotary_embedding(k_r, ds, cfg.rope_theta,
                                      name=p + "_k_rope")
    k_r = layers.expand(k_r, [1, H, 1, 1])
    k = layers.concat([heads_first(k_n), k_r], axis=3)
    ctx = layers.fused_attention(q, k, heads_first(v),
                                 scale=(dn + ds) ** -0.5, causal=True,
                                 name=p + "_core")
    return proj(layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                               [0, 0, H * dv]), cfg.hidden_size, p + "_o",
                cfg)


def mlp(x, width, cfg, p):
    """The bias-free SwiGLU ``W_down (silu(W_gate x) * W_up x)`` at
    ``width``: parameters ``<p>_gate_w``, ``<p>_up_w``, ``<p>_down_w``."""
    return proj(layers.swiglu(proj(x, width, p + "_gate", cfg),
                              proj(x, width, p + "_up", cfg),
                              name=p + "_act"),
                cfg.hidden_size, p + "_down", cfg)


def routed_and_shared_experts(x, cfg, p):
    """``routed_experts`` plus the ``num_shared_experts`` shared experts,
    whole, as one SwiGLU of ``moe_intermediate_size * num_shared_experts``
    (``<p>_shared_*``)."""
    return layers.elementwise_add(
        routed_experts(x, cfg, p),
        mlp(x, cfg.moe_intermediate_size * cfg.num_shared_experts, cfg,
            p + "_shared"), name=p + "_sum")


def routed_experts(x, cfg, p):
    """The router over all ``num_experts_total`` experts and the part of
    the result that the ``num_experts`` held here (from ``expert_offset``
    on) give (``fluid/ops/moe_ops.py``). A config with
    ``moe_router_activation_func`` ``"sigmoid"`` gets the sigmoid router:
    its selection-only bias (``<p>_router_bias``, frozen, zero) and its
    ``routed_scaling_factor``."""
    more = {}
    if getattr(cfg, "moe_router_activation_func", "softmax") == "sigmoid":
        more = dict(
            scoring="sigmoid",
            bias_attr=fluid.ParamAttr(name=p + "_router_bias",
                                      trainable=False),
            routed_scaling_factor=cfg.routed_scaling_factor)
    ids, wts = layers.moe_route(
        x, cfg.num_experts_total, cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        param_attr=attr(p + "_router_w", cfg), name=p + "_route", **more)
    return layers.moe_experts(
        x, ids, wts, cfg.num_experts, cfg.moe_intermediate_size,
        expert_offset=cfg.expert_offset, experts_total=cfg.num_experts_total,
        gate_attr=attr(p + "_gate_w", cfg), up_attr=attr(p + "_up_w", cfg),
        down_attr=attr(p + "_down_w", cfg), name=p + "_experts")


def _head_loss(hidden, labels, cfg, name=None):
    """The mean cross-entropy of the head ``lm_head`` over ``hidden``
    [B, S', h] against ``labels`` [B, S']."""
    logits = proj(hidden, cfg.vocab_size, "lm_head", cfg)
    ce = layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, cfg.vocab_size]),
        layers.reshape(labels, [-1, 1]))
    return layers.mean(ce, name=name)


def multi_token_prediction(hidden, labels, cfg, layer):
    """One multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2; the released ``nextn`` layer), parameters ``mtp_0_*``:
    ``m_i = W_eh [rms(Emb(labels_i); w_e) ; rms(hidden_i; w_h)]`` through
    the main model's embedding ``embed_tokens`` (``labels_i`` is token
    ``i + 1``), then ``layer(m, cfg, "mtp_0")`` - one more decoder layer
    of the model's own, at positions i. Returns ``(rms(m'; w_s), m')``:
    what the shared head reads to predict token ``i + 2``, and the
    module's layer boundary."""
    h = cfg.hidden_size
    emb = layers.embedding(layers.unsqueeze(labels, [2]),
                           [cfg.vocab_size, h],
                           param_attr=attr("embed_tokens", cfg))
    m = proj(layers.concat([rms(emb, "mtp_0_enorm", cfg, False),
                            rms(hidden, "mtp_0_hnorm", cfg, False)],
                           axis=2), h, "mtp_0_eh_proj", cfg)
    m = layer(m, cfg, "mtp_0")
    return rms(m, "mtp_0_head_norm", cfg, False), m


def build_train_program(decoder, cfg, batch, seq_len, lr=1e-4, use_amp=True,
                        recompute=False, seed=7, mtp=None):
    """Next-token cross-entropy over every position of ``tokens`` /
    ``labels`` [batch, seq_len] (the caller shifts) round ``decoder(tokens,
    cfg) -> (hidden, boundaries)``, Adam, AMP bf16 over float32 masters
    where ``use_amp``, and with ``recompute`` the ``RecomputeOptimizer``'s
    checkpoints at the layer boundaries. Returns ``(main, startup,
    loss)``.

    ``mtp``: the model's decoder layer ``mtp(x, cfg, p) -> x`` for ONE
    multi-token-prediction module (``cfg.num_nextn_predict_layers`` 1),
    built inside ``fluid.name_scope("mtp")`` so that a device trace can
    say what it costs (``profiler.newest_step_modules``): the module
    (``multi_token_prediction``) over the main model's normed ``hidden``,
    the SAME head ``lm_head`` over its first ``seq_len - 1`` positions
    against ``labels`` from position 1 (the last position has no target
    and is left out), and the loss ``loss_main + mtp_loss_factor *
    loss_mtp``, the two terms the program variables named from
    ``loss_main`` and ``loss_mtp``. Its layer boundary is a checkpoint
    too, and its loss reaches the main model through ``hidden``, the
    embedding and the head. Without ``mtp`` the program is the plain
    next-token one."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[batch, seq_len], dtype="int64",
                             append_batch_size=False)
        labels = layers.data("labels", shape=[batch, seq_len], dtype="int64",
                             append_batch_size=False)
        hidden, boundaries = decoder(tokens, cfg)
        if mtp is None:
            loss = _head_loss(hidden, labels, cfg)
        else:
            assert cfg.num_nextn_predict_layers == 1, \
                cfg.num_nextn_predict_layers
            with fluid.name_scope("mtp"):
                normed, boundary = multi_token_prediction(hidden, labels,
                                                          cfg, mtp)
            boundaries = boundaries + [boundary]
            loss_main = _head_loss(hidden, labels, cfg, name="loss_main")
            with fluid.name_scope("mtp"):
                loss_mtp = _head_loss(
                    layers.slice(normed, axes=[1], starts=[0],
                                 ends=[seq_len - 1]),
                    layers.slice(labels, axes=[1], starts=[1],
                                 ends=[seq_len]), cfg, name="loss_mtp")
            loss = layers.elementwise_add(
                loss_main, layers.scale(loss_mtp, cfg.mtp_loss_factor),
                name="loss")
        opt = optimizer.Adam(learning_rate=lr)
        if recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(boundaries)
        if use_amp:
            from ..fluid.contrib import mixed_precision

            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss
