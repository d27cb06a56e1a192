"""AMP op lists: which ops run in low precision (bf16/fp16), which must
stay fp32, and which follow their inputs.

Parity: reference ``contrib/mixed_precision/fp16_lists.py``. TPU note: the
white list is the MXU ops (matmul/conv) — on TPU the low-precision dtype of
choice is bfloat16, whose fp32-range exponent makes loss scaling optional.
"""

__all__ = ["AutoMixedPrecisionLists"]

# ops that benefit from low precision (MXU-bound)
white_list = {
    "conv2d", "conv3d", "depthwise_conv2d", "conv2d_transpose",
    "conv3d_transpose", "matmul", "mul", "bmm",
    # the pallas kernel does its matmuls in the INPUT dtype with f32
    # accumulation (softmax stays f32 internally), so bf16 inputs hit
    # the MXU at full rate
    "fused_multihead_attention",
    "fused_multihead_attention_packed",
}

# numerically sensitive ops kept in fp32
black_list = {
    "exp", "log", "square", "softmax", "log_softmax", "mean", "sum",
    "reduce_sum", "reduce_mean", "cos_sim", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "cross_entropy",
    "group_norm", "instance_norm", "l2_normalize",
    # the router: f32 logits and softmax over all experts (a bf16 pass
    # flips near-tied choices); its weights go on in f32
    "moe_route",
}

# everything else follows its inputs (elementwise, activations, shape ops)
gray_list = {
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min", "relu", "gelu",
    "tanh", "sigmoid", "dropout", "pool2d", "pool3d", "reshape", "transpose",
    "concat", "split", "slice", "flatten", "squeeze", "unsqueeze", "stack",
    "scale", "cast", "pad", "gather", "lookup_table", "lookup_table_v2",
    # TPU deviation from the reference (which blacklists both for
    # fp16): the norms follow their inputs. bf16 shares fp32's exponent
    # and both lowerings compute stats and normalize in f32 regardless
    # of the activation dtype (ops/nn.py), so bf16 norm I/O is safe —
    # and norm I/O dominates HBM traffic (all of ResNet's activations;
    # 24 layer_norms per BERT step). A caller that wants the reference
    # behavior passes custom_black_list=["batch_norm", "layer_norm"].
    "batch_norm", "layer_norm",
    # rms_norm as layer_norm (f32 statistics inside, f32 weight). The
    # rest follow their activations, which the projections made low:
    # gated_delta_rule keeps A_log / dt_bias, its decays, its triangular
    # system and its state in f32 and feeds the matmuls in the input
    # dtype; moe_experts keeps the router's weights f32 and runs the
    # grouped GEMMs in the input dtype; sparse_index feeds its score
    # matmuls in the input dtype, accumulates, weighs and compares in f32
    "rms_norm", "swiglu", "rotary_embedding", "causal_conv1d",
    "gated_delta_rule", "moe_experts", "sparse_index",
}


class AutoMixedPrecisionLists:
    """User-tunable white/black lists (reference ``fp16_lists.py:23``)."""

    def __init__(self, custom_white_list=None, custom_black_list=None,
                 custom_black_varnames=None):
        self.white_list = set(white_list)
        self.black_list = set(black_list)
        self.gray_list = set(gray_list)
        self.black_varnames = set(custom_black_varnames or [])
        for t in custom_white_list or []:
            self.black_list.discard(t)
            self.white_list.add(t)
        for t in custom_black_list or []:
            self.white_list.discard(t)
            self.black_list.add(t)
