"""What the decoder-only model files here share (``qwen3_next.py``,
``keye_vl2.py``, ``kimi_linear.py``): a parameter's attribute, the
bias-free projection, the RMS norm, one expert-parallel rank's share of a
routed expert layer, and the training program round a decoder (next-token
loss, Adam, recomputation at the layer boundaries, AMP). A config ``cfg``
is any object with the published key names these read; ``DecoderConfig``
is what the model files' config classes share."""

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


def build_train_program(decoder, cfg, batch, seq_len, lr=1e-4, use_amp=True,
                        recompute=False, seed=7):
    """Next-token cross-entropy over every position of ``tokens`` /
    ``labels`` [batch, seq_len] (the caller shifts) round ``decoder(tokens,
    cfg) -> (hidden, boundaries)``, Adam, AMP bf16 over float32 masters
    where ``use_amp``, and with ``recompute`` the ``RecomputeOptimizer``'s
    checkpoints at the layer boundaries. Returns ``(main, startup,
    loss)``."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[batch, seq_len], dtype="int64",
                             append_batch_size=False)
        labels = layers.data("labels", shape=[batch, seq_len], dtype="int64",
                             append_batch_size=False)
        hidden, boundaries = decoder(tokens, cfg)
        logits = proj(hidden, cfg.vocab_size, "lm_head", cfg)
        ce = layers.softmax_with_cross_entropy(
            layers.reshape(logits, [-1, cfg.vocab_size]),
            layers.reshape(labels, [-1, 1]))
        loss = layers.mean(ce)
        opt = optimizer.Adam(learning_rate=lr)
        if recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(boundaries)
        if use_amp:
            from ..fluid.contrib import mixed_precision

            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss
