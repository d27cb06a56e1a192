"""Qwen3-Next: a decoder-only causal LM whose token mixers are Gated
DeltaNet layers (linear attention) three times in four and gated
grouped-query softmax attention the fourth, each followed by a sparse
expert layer with a shared expert. Built from ``fluid.layers`` only; a
training step is ``fluid.Executor().run(main, feed, fetch_list=[loss])``.

Layer ``i`` (``h`` the hidden size, ``rms`` zero-centred)::

    u = x + mixer_i(rms(x; w1));  y = u + moe(rms(u; w2))

with full attention where ``(i + 1) % full_attention_interval == 0``. The
expert layer is one expert-parallel rank's share: the router scores all
``num_experts_total`` experts and keeps ``num_experts_per_tok``; this rank
holds ``num_experts`` of them from ``expert_offset`` on and computes their
part of the result (``fluid/ops/moe_ops.py``). Parameter names are fixed
(``layer_3_attn_q_w``), so a reference can find its leaves.

The fused projections' columns lie ``[q | k | v | z]`` and ``[b | a]`` in a
DeltaNet layer (heads in order inside each part) and ``[q | gate]`` per
head in an attention layer's query projection.
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

from . import decoder_blocks
from .decoder_blocks import attr as _attr
from .decoder_blocks import proj as _proj
from .decoder_blocks import rms as _rms


class Qwen3NextConfig(decoder_blocks.DecoderConfig):
    """The keys of the model's ``config.json`` that shape a step, under
    their published names. ``num_experts`` counts the experts HELD here;
    ``num_experts_total`` is the router's width."""

    def __init__(self, **kw):
        self.vocab_size = 151936
        self.hidden_size = 2048
        self.num_hidden_layers = 48
        self.full_attention_interval = 4
        self.num_attention_heads = 16
        self.num_key_value_heads = 2
        self.head_dim = 256
        self.partial_rotary_factor = 0.25
        self.rope_theta = 10000000.0
        self.rms_norm_eps = 1e-6
        self.linear_num_key_heads = 16
        self.linear_num_value_heads = 32
        self.linear_key_head_dim = 128
        self.linear_value_head_dim = 128
        self.linear_conv_kernel_dim = 4
        self.num_experts = 512
        self.num_experts_total = 512
        self.expert_offset = 0
        self.num_experts_per_tok = 10
        self.norm_topk_prob = True
        self.moe_intermediate_size = 512
        self.shared_expert_intermediate_size = 512
        self.initializer_range = 0.02
        self.gdn_chunk_size = 64
        self._override(kw)

    def is_full_attention(self, i):
        return (i + 1) % self.full_attention_interval == 0


def _gated_delta_net(x, cfg, p):
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    kd, vd = Hk * dk, Hv * dv
    qkvz = _proj(x, 2 * kd + 2 * vd, p + "_qkvz", cfg)
    ba = _proj(x, 2 * Hv, p + "_ba", cfg)
    qkv, z = layers.split(qkvz, [2 * kd + vd, vd], dim=-1, name=p + "_split")
    qkv = layers.causal_conv1d(
        qkv, cfg.linear_conv_kernel_dim,
        param_attr=_attr(p + "_conv_w", cfg), act="swish", name=p + "_conv")
    q, k, v = layers.split(qkv, [kd, kd, vd], dim=-1, name=p + "_qkv")
    b, a = layers.split(ba, 2, dim=-1, name=p + "_ba_split")
    o = layers.gated_delta_rule(
        layers.reshape(q, [0, 0, Hk, dk]), layers.reshape(k, [0, 0, Hk, dk]),
        layers.reshape(v, [0, 0, Hv, dv]), a, b,
        a_log_attr=fluid.ParamAttr(name=p + "_a_log"),
        dt_bias_attr=fluid.ParamAttr(name=p + "_dt_bias"),
        chunk_size=cfg.gdn_chunk_size, name=p + "_rule")
    # the gated norm on the way out: plain-weight RMS norm over the head
    # dim, times silu(z)
    o = layers.swiglu(layers.reshape(z, [0, 0, Hv, dv]),
                      _rms(o, p + "_norm", cfg, zero_centered=False),
                      name=p + "_gate")
    return _proj(layers.reshape(o, [0, 0, vd]), cfg.hidden_size, p + "_out",
                 cfg)


def _gated_attention(x, cfg, p):
    H, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    qg = layers.reshape(_proj(x, H * 2 * d, p + "_q", cfg), [0, 0, H, 2 * d])
    q, gate = layers.split(qg, 2, dim=-1, name=p + "_q_split")
    k = layers.reshape(_proj(x, Hkv * d, p + "_k", cfg), [0, 0, Hkv, d])
    v = layers.reshape(_proj(x, Hkv * d, p + "_v", cfg), [0, 0, Hkv, d])
    rd = int(d * cfg.partial_rotary_factor)

    def heads_first(t):
        return layers.transpose(t, [0, 2, 1, 3])        # [B, H, S, d]

    q = layers.rotary_embedding(
        heads_first(_rms(q, p + "_q_norm", cfg)), rd, cfg.rope_theta,
        name=p + "_q_rope")
    k = layers.rotary_embedding(
        heads_first(_rms(k, p + "_k_norm", cfg)), rd, cfg.rope_theta,
        name=p + "_k_rope")
    ctx = layers.fused_attention(q, k, heads_first(v), scale=d ** -0.5,
                                 causal=True, num_kv_heads=Hkv,
                                 name=p + "_core")
    ctx = layers.elementwise_mul(layers.transpose(ctx, [0, 2, 1, 3]),
                                 layers.sigmoid(gate), name=p + "_gate")
    return _proj(layers.reshape(ctx, [0, 0, H * d]), cfg.hidden_size,
                 p + "_o", cfg)


def _moe(x, cfg, p):
    routed = decoder_blocks.routed_experts(x, cfg, p)
    f = cfg.shared_expert_intermediate_size
    shared = _proj(layers.swiglu(_proj(x, f, p + "_shared_gate", cfg),
                                 _proj(x, f, p + "_shared_up", cfg),
                                 name=p + "_shared_act"),
                   cfg.hidden_size, p + "_shared_down", cfg)
    shared = layers.elementwise_mul(
        shared, layers.sigmoid(_proj(x, 1, p + "_shared_router", cfg)),
        name=p + "_shared_gated")
    return layers.elementwise_add(routed, shared, name=p + "_sum")


def decoder(tokens, cfg):
    """``tokens`` [B, S] int64 -> (hidden states after the final norm
    [B, S, h], the residual stream after each layer)."""
    x = layers.embedding(layers.unsqueeze(tokens, [2]),
                         [cfg.vocab_size, cfg.hidden_size],
                         param_attr=_attr("embed_tokens", cfg))
    boundaries = []
    for i in range(cfg.num_hidden_layers):
        p = "layer_%d" % i
        mixer = _gated_attention if cfg.is_full_attention(i) \
            else _gated_delta_net
        kind = "_attn" if cfg.is_full_attention(i) else "_gdn"
        x = layers.elementwise_add(
            x, mixer(_rms(x, p + "_norm1", cfg), cfg, p + kind),
            name=p + "_res1")
        x = layers.elementwise_add(
            x, _moe(_rms(x, p + "_norm2", cfg), cfg, p + "_moe"),
            name=p + "_res2")
        boundaries.append(x)
    return _rms(x, "final_norm", cfg), boundaries


def build_train_program(cfg, batch, seq_len, lr=1e-4, use_amp=True,
                        recompute=False, seed=7):
    """``decoder_blocks.build_train_program`` round ``decoder``."""
    return decoder_blocks.build_train_program(
        decoder, cfg, batch, seq_len, lr=lr, use_amp=use_amp,
        recompute=recompute, seed=seed)
