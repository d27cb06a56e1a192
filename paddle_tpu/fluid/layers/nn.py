"""Declarative NN layers — build ops into the default main program.

Parity: reference ``python/paddle/fluid/layers/nn.py`` (146 functions; SURVEY
Appendix A). Layer functions validate args, create parameters via
LayerHelper, and append ops; all math happens in the lowered XLA program.
"""

import numpy as np

from .. import framework
from ..framework import Variable
from ..layer_helper import LayerHelper

__all__ = [
    "fc", "embedding", "conv2d", "conv3d", "conv2d_transpose", "conv3d_transpose",
    "softmax", "pool2d", "pool3d", "adaptive_pool2d", "batch_norm", "instance_norm",
    "layer_norm", "group_norm", "spectral_norm", "data_norm",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_all", "reduce_any", "dropout", "split", "l2_normalize", "matmul", "topk",
    "transpose", "im2sequence", "row_conv", "multiplex", "one_hot", "reshape",
    "squeeze", "unsqueeze", "lrn", "pad", "pad2d", "pad_constant_like", "label_smooth",
    "image_resize", "resize_bilinear", "resize_nearest", "resize_trilinear",
    "gather", "gather_nd", "scatter", "scatter_nd_add", "random_crop", "mean_iou",
    "relu", "selu", "log", "crop", "elu", "relu6", "pow", "stanh", "hard_sigmoid",
    "swish", "prelu", "brelu", "leaky_relu", "soft_relu", "flatten", "stack",
    "unstack", "expand", "expand_as", "scale", "elementwise_add", "elementwise_div",
    "elementwise_sub", "elementwise_mul", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod", "elementwise_floordiv",
    "uniform_random_batch_size_like", "gaussian_random", "sampling_id",
    "gaussian_random_batch_size_like", "sum", "slice", "strided_slice", "shape",
    "rank", "size", "logical_and", "logical_or", "logical_xor", "logical_not",
    "clip", "clip_by_norm", "mean", "mul", "maxout", "space_to_depth",
    "affine_grid", "affine_channel", "hash", "grid_sampler", "log_loss",
    "add_position_encoding", "bilinear_tensor_product", "shuffle_channel",
    "temporal_shift", "pixel_shuffle", "where", "sign", "unfold", "shard_index",
    "hard_swish", "uniform_random", "gelu", "erf", "topk", "unique",
    "autoincreased_step_counter", "smooth_l1", "dice_loss", "py_func",
    "linear_chain_crf", "crf_decoding", "ctc_greedy_decoder",
    "shard_tensor", "fused_attention",
    "einsum", "rms_norm", "swiglu", "rotary_embedding", "sparse_index",
    "causal_conv1d", "gated_short_conv",
    "gated_delta_rule", "moe_route", "moe_experts",
]


def _data_type(x):
    return framework.dtype_str(x.dtype)


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully-connected layer (reference ``layers/nn.py`` fc): flattens input
    to 2-D, matmuls against a (in, size) weight — MXU-friendly — adds bias,
    applies activation."""
    helper = LayerHelper("fc", **locals())
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for inp in inputs:
        in_features = int(np.prod(inp.shape[num_flatten_dims:]))
        w = helper.create_parameter(param_attr, [in_features, size], _data_type(inp))
        out = helper.create_variable_for_type_inference(inp.dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [inp], "Y": [w]},
            outputs={"Out": [out]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(inputs[0].dtype)
        helper.append_op(type="sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]})
    pre_act = _append_bias(helper, pre_bias, bias_attr, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act, act)


def _append_bias(helper, x, bias_attr, dim_start=1, channel_dim=None):
    if bias_attr is False:
        return x
    if channel_dim is not None:
        bias_size = [x.shape[channel_dim]] if x.shape and len(x.shape) > channel_dim else [1]
        axis = channel_dim
    else:
        bias_size = [int(np.prod(x.shape[dim_start:]))] if x.shape else [1]
        axis = dim_start
    b = helper.create_parameter(bias_attr, bias_size, _data_type(x), is_bias=True)
    if b is None:
        return x
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="elementwise_add",
        inputs={"X": [x], "Y": [b]},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32",
              table_lr=0.01, table_optimizer="sgd", residence=None):
    """Embedding lookup. ``is_sparse=True`` routes onto the sparse
    embedding engine (paddle_tpu.embedding): the device tier's
    dedup-gather ``embedding_lookup`` op with a SelectedRows backward and
    fused row-sparse optimizer updates. ``residence`` picks the tier
    explicitly ("device" | "host"); by default a lookup whose param name
    has a registered ``HostEmbeddingTable`` goes to the host tier (table
    in host RAM behind a fixed HBM cache). ``is_distributed=True`` stays
    the legacy parameter-server shim."""
    helper = LayerHelper("embedding", **locals())
    if is_distributed:
        # PS tier (reference distributed_lookup_table_op.cc): the table is a
        # host-resident sharded store, NOT a device Parameter. Rows are
        # pulled via host callback; grads are pushed to the host optimizer
        # (table_lr/table_optimizer) by a distributed_push op appended in
        # append_backward. A distributed_table_init op in the STARTUP
        # program resets the host store like device params.
        from ...distributed import ps

        from .. import unique_name

        name = (param_attr.name if param_attr is not None
                and getattr(param_attr, "name", None) else
                unique_name.generate("dist_emb"))
        ps.ensure_table(name, size[0], size[1])
        helper.startup_program.global_block().append_op(
            "distributed_table_init", attrs={"table_name": name})
        out = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="distributed_lookup_table",
            inputs={"Ids": [input]},
            outputs={"Out": [out]},
            attrs={"table_name": name, "dim": int(size[1]),
                   "lr": float(table_lr), "optimizer": table_optimizer,
                   "padding_idx": -1 if padding_idx is None else padding_idx,
                   "dtype": dtype},
        )
        return out
    pname = (param_attr.name if param_attr is not None
             and getattr(param_attr, "name", None) else None)
    if residence not in (None, "device", "host"):
        raise ValueError(
            "embedding residence must be None, 'device' or 'host', got %r"
            % (residence,))
    if residence is None and pname is not None:
        from ... import embedding as _embedding

        if _embedding.has_host_table(pname):
            residence = "host"
    if residence == "host":
        if pname is None:
            raise ValueError(
                "residence='host' needs param_attr with a name matching a "
                "registered HostEmbeddingTable")
        from ... import embedding as _embedding
        from ...embedding.host import append_host_lookup

        return append_host_lookup(helper, input, size,
                                  _embedding.get_host_table(pname),
                                  padding_idx, dtype)
    w = helper.create_parameter(param_attr, size, dtype)
    out = helper.create_variable_for_type_inference(dtype)
    if is_sparse:
        # engine device tier: dedup-gather lookup; backward stays the
        # SelectedRows pair, the optimizer applies the fused row update
        helper.append_op(
            type="embedding_lookup",
            inputs={"W": [w], "Ids": [input]},
            outputs={"Out": [out]},
            attrs={
                "is_sparse": True,
                "dedup": True,
                "padding_idx": -1 if padding_idx is None else padding_idx,
            },
        )
        return out
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={
            "is_sparse": is_sparse,
            "is_distributed": is_distributed,
            "padding_idx": -1 if padding_idx is None else padding_idx,
        },
    )
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d", **locals())
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    num_channels = (input.shape[-1] if data_format == "NHWC"
                    else input.shape[1])
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)
    from ..initializer import Normal

    fan = num_channels * filter_size[0] * filter_size[1] // groups
    w = helper.create_parameter(
        param_attr, filter_shape, _data_type(input),
        default_initializer=Normal(0.0, (2.0 / fan) ** 0.5),
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": [stride, stride] if isinstance(stride, int) else list(stride),
            "paddings": [padding, padding] if isinstance(padding, int) else list(padding),
            "dilations": [dilation, dilation] if isinstance(dilation, int) else list(dilation),
            "groups": groups,
            "data_format": data_format,
        },
    )
    out = _append_bias(helper, out, bias_attr,
                       channel_dim=-1 if data_format == "NHWC" else 1)
    return helper.append_activation(out, act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d", **locals())
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size] * 3
    num_channels = input.shape[1]
    w = helper.create_parameter(
        param_attr, [num_filters, num_channels // groups] + list(filter_size),
        _data_type(input),
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv3d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": [stride] * 3 if isinstance(stride, int) else list(stride),
            "paddings": [padding] * 3 if isinstance(padding, int) else list(padding),
            "dilations": [dilation] * 3 if isinstance(dilation, int) else list(dilation),
            "groups": groups,
        },
    )
    out = _append_bias(helper, out, bias_attr, channel_dim=1)
    return helper.append_activation(out, act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", **locals())
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    num_channels = input.shape[1]
    w = helper.create_parameter(
        param_attr, [num_channels, num_filters // groups] + list(filter_size),
        _data_type(input),
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": [stride, stride] if isinstance(stride, int) else list(stride),
            "paddings": [padding, padding] if isinstance(padding, int) else list(padding),
            "dilations": [dilation, dilation] if isinstance(dilation, int) else list(dilation),
            "groups": groups,
        },
    )
    out = _append_bias(helper, out, bias_attr, channel_dim=1)
    return helper.append_activation(out, act)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d_transpose", **locals())
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size] * 3
    num_channels = input.shape[1]
    w = helper.create_parameter(
        param_attr,
        [num_channels, num_filters // groups] + list(filter_size),
        _data_type(input),
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="conv3d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={
            "strides": [stride] * 3 if isinstance(stride, int) else list(stride),
            "paddings": [padding] * 3 if isinstance(padding, int) else list(padding),
            "dilations": [dilation] * 3 if isinstance(dilation, int)
            else list(dilation),
            "groups": groups,
        },
    )
    out = _append_bias(helper, out, bias_attr, channel_dim=1)
    return helper.append_activation(out, act)


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="softmax", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False, name=None,
           exclusive=True, adaptive=False, data_format="NCHW"):
    helper = LayerHelper("pool2d", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": [pool_size, pool_size] if isinstance(pool_size, int) else list(pool_size),
            "strides": [pool_stride, pool_stride] if isinstance(pool_stride, int) else list(pool_stride),
            "paddings": [pool_padding, pool_padding] if isinstance(pool_padding, int) else list(pool_padding),
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
            "adaptive": adaptive,
            "data_format": data_format,
        },
    )
    return out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, ceil_mode=False, name=None, exclusive=True):
    helper = LayerHelper("pool3d", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool3d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": [pool_size] * 3 if isinstance(pool_size, int) else list(pool_size),
            "strides": [pool_stride] * 3 if isinstance(pool_stride, int) else list(pool_stride),
            "paddings": [pool_padding] * 3 if isinstance(pool_padding, int) else list(pool_padding),
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
        },
    )
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False, name=None):
    return pool2d(input, pool_size=pool_size, pool_type=pool_type, adaptive=True)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    helper = LayerHelper("batch_norm", **locals())
    dtype = _data_type(input)
    ch = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    from ..initializer import Constant

    scale = helper.create_parameter(param_attr, [ch], dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, [ch], dtype, is_bias=True)
    # running stats: persistable, non-trainable
    mean = _create_persistable_stat(helper, moving_mean_name, [ch], dtype, 0.0)
    var = _create_persistable_stat(helper, moving_variance_name, [ch], dtype, 1.0)
    out = helper.create_variable_for_type_inference(input.dtype)
    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [var],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout, "use_global_stats": use_global_stats},
    )
    return helper.append_activation(out, act)


def _create_persistable_stat(helper, name, shape, dtype, init_val):
    from .. import unique_name as un
    from ..initializer import Constant

    name = name or un.generate(helper.name_prefix + ".stat")
    var = helper.main_program.global_block().create_var(
        name=name, shape=shape, dtype=dtype, persistable=True, stop_gradient=True
    )
    sb = helper.startup_program.global_block()
    sv = sb.create_var(name=name, shape=shape, dtype=dtype, persistable=True,
                       stop_gradient=True)
    Constant(init_val)(sv, sb)
    return var


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("instance_norm", **locals())
    dtype = _data_type(input)
    ch = input.shape[1]
    from ..initializer import Constant

    scale = helper.create_parameter(param_attr, [ch], dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, [ch], dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="instance_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias]},
        outputs={"Y": [out]},
        attrs={"epsilon": epsilon},
    )
    return out


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm", **locals())
    dtype = _data_type(input)
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    from ..initializer import Constant

    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, norm_shape, dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, norm_shape, dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out, act)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", **locals())
    dtype = _data_type(input)
    ch = input.shape[1]
    from ..initializer import Constant

    scale = helper.create_parameter(param_attr, [ch], dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, [ch], dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="group_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias]},
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"epsilon": epsilon, "groups": groups},
    )
    return helper.append_activation(out, act)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", **locals())
    dtype = _data_type(weight)
    h = weight.shape[dim]
    w = int(np.prod(weight.shape)) // h
    from ..initializer import Normal

    u = helper.create_parameter(None, [h], dtype, default_initializer=Normal(0.0, 1.0))
    v = helper.create_parameter(None, [w], dtype, default_initializer=Normal(0.0, 1.0))
    u.stop_gradient = True
    v.stop_gradient = True
    out = helper.create_variable_for_type_inference(weight.dtype)
    helper.append_op(
        type="spectral_norm",
        inputs={"Weight": [weight], "U": [u], "V": [v]},
        outputs={"Out": [out]},
        attrs={"dim": dim, "power_iters": power_iters, "eps": eps},
    )
    return out


def data_norm(input, act=None, epsilon=1e-5, param_attr=None, name=None):
    helper = LayerHelper("data_norm", **locals())
    dtype = _data_type(input)
    ch = input.shape[-1]
    from ..initializer import Constant

    batch_size = _create_persistable_stat(helper, None, [ch], dtype, 1e4)
    batch_sum = _create_persistable_stat(helper, None, [ch], dtype, 0.0)
    batch_square = _create_persistable_stat(helper, None, [ch], dtype, 1e4)
    out = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    scales = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="data_norm",
        inputs={"X": [input], "BatchSize": [batch_size], "BatchSum": [batch_sum],
                "BatchSquareSum": [batch_square]},
        outputs={"Y": [out], "Means": [means], "Scales": [scales]},
        attrs={"epsilon": epsilon},
    )
    return helper.append_activation(out, act)


def _reduce_layer(op_type, input, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if dim is None:
        attrs = {"reduce_all": True, "dim": [0], "keep_dim": keep_dim}
    else:
        attrs = {"reduce_all": False,
                 "dim": dim if isinstance(dim, (list, tuple)) else [dim],
                 "keep_dim": keep_dim}
    helper.append_op(type=op_type, inputs={"X": [input]}, outputs={"Out": [out]}, attrs=attrs)
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_prod", input, dim, keep_dim, name)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_all", input, dim, keep_dim, name)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_any", input, dim, keep_dim, name)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "dropout_implementation": dropout_implementation},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", **locals())
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": dim}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": dim}
    outs = [helper.create_variable_for_type_inference(input.dtype) for _ in range(n)]
    helper.append_op(type="split", inputs={"X": [input]}, outputs={"Out": outs}, attrs=attrs)
    return outs


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="l2_normalize",
        inputs={"X": [x]},
        outputs={"Out": [out], "Norm": [norm]},
        attrs={"axis": axis, "epsilon": epsilon},
    )
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="matmul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
               "alpha": float(alpha)},
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", **locals())
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": k},
    )
    return values, indices


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="transpose", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": list(perm)})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper("im2sequence", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="im2sequence",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "kernels": [filter_size, filter_size] if isinstance(filter_size, int) else list(filter_size),
            "strides": [stride, stride] if isinstance(stride, int) else list(stride),
            "paddings": [padding] * 4 if isinstance(padding, int) else list(padding),
        },
    )
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", **locals())
    w = helper.create_parameter(
        param_attr, [future_context_size + 1, input.shape[-1]], _data_type(input)
    )
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="row_conv", inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]})
    return helper.append_activation(out, act)


def multiplex(inputs, index):
    helper = LayerHelper("multiplex", **locals())
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(type="multiplex", inputs={"X": inputs, "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot", **locals())
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"depth": depth})
    return out


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="reshape", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape)})
    return helper.append_activation(out, act)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="squeeze", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="unsqueeze", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes)})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="lrn", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "pad_value": float(pad_value)})
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": float(pad_value)})
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", **locals())
    out = helper.create_variable_for_type_inference(y.dtype)
    helper.append_op(type="pad_constant_like", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"pad_value": float(pad_value)})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="label_smooth", inputs={"X": [label]}, outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1):
    op_type = {"BILINEAR": "bilinear_interp", "NEAREST": "nearest_interp",
               "TRILINEAR": "trilinear_interp"}[resample]
    helper = LayerHelper(op_type, **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"align_corners": align_corners, "align_mode": align_mode}
    if out_shape is not None:
        if op_type == "trilinear_interp":
            attrs["out_d"], attrs["out_h"], attrs["out_w"] = out_shape
        else:
            attrs["out_h"], attrs["out_w"] = out_shape
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op(type=op_type, inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "BILINEAR", actual_shape,
                        align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, "NEAREST", actual_shape,
                        align_corners)


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "TRILINEAR", actual_shape,
                        align_corners, align_mode)


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather_nd", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="scatter",
                     inputs={"X": [input], "Ids": [index], "Updates": [updates]},
                     outputs={"Out": [out]}, attrs={"overwrite": overwrite})
    return out


def scatter_nd_add(ref, index, updates, name=None):
    helper = LayerHelper("scatter_nd_add", **locals())
    out = helper.create_variable_for_type_inference(ref.dtype)
    helper.append_op(type="scatter_nd_add",
                     inputs={"X": [ref], "Index": [index], "Updates": [updates]},
                     outputs={"Out": [out]})
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="random_crop", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape)})
    return out


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou", **locals())
    iou = helper.create_variable_for_type_inference("float32")
    wrong = helper.create_variable_for_type_inference("int32")
    correct = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="mean_iou",
        inputs={"Predictions": [input], "Labels": [label]},
        outputs={"OutMeanIou": [iou], "OutWrong": [wrong], "OutCorrect": [correct]},
        attrs={"num_classes": num_classes},
    )
    return iou, wrong, correct


def _unary_layer(op_type, x, name=None, **attrs):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type=op_type, inputs={"X": [x]}, outputs={"Out": [out]}, attrs=attrs)
    return out


def relu(x, name=None):
    return _unary_layer("relu", x, name)


def selu(x, scale=None, alpha=None, name=None):
    attrs = {}
    if scale is not None:
        attrs["scale"] = scale
    if alpha is not None:
        attrs["alpha"] = alpha
    return _unary_layer("selu", x, name, **attrs)


def log(x, name=None):
    return _unary_layer("log", x, name)


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if isinstance(shape, Variable):
        raise NotImplementedError("dynamic crop shape unsupported (XLA static shapes)")
    offsets = offsets or [0] * len(x.shape)
    helper.append_op(
        type="slice",
        inputs={"Input": [x]},
        outputs={"Out": [out]},
        attrs={"axes": list(range(len(x.shape))),
               "starts": list(offsets),
               "ends": [o + s for o, s in zip(offsets, shape)]},
    )
    return out


crop_tensor = crop


def elu(x, alpha=1.0, name=None):
    return _unary_layer("elu", x, name, alpha=alpha)


def relu6(x, threshold=6.0, name=None):
    return _unary_layer("relu6", x, name, threshold=threshold)


def pow(x, factor=1.0, name=None):
    return _unary_layer("pow", x, name, factor=factor)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _unary_layer("stanh", x, name, scale_a=scale_a, scale_b=scale_b)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _unary_layer("hard_sigmoid", x, name, slope=slope, offset=offset)


def swish(x, beta=1.0, name=None):
    return _unary_layer("swish", x, name, beta=beta)


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", **locals())
    alpha_shape = [1]
    if mode == "channel":
        alpha_shape = [x.shape[1]]
    elif mode == "element":
        alpha_shape = list(x.shape[1:])
    from ..initializer import Constant

    alpha = helper.create_parameter(param_attr, alpha_shape, _data_type(x),
                                    default_initializer=Constant(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _unary_layer("brelu", x, name, t_min=t_min, t_max=t_max)


def leaky_relu(x, alpha=0.02, name=None):
    return _unary_layer("leaky_relu", x, name, alpha=alpha)


def soft_relu(x, threshold=40.0, name=None):
    return _unary_layer("soft_relu", x, name, threshold=threshold)


def gelu(x, approximate=False, name=None):
    return _unary_layer("gelu", x, name, approximate=approximate)


def erf(x, name=None):
    return _unary_layer("erf", x, name)


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="flatten", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack", **locals())
    x = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(type="stack", inputs={"X": x}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack", **locals())
    num = num or x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in range(num)]
    helper.append_op(type="unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis})
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return out


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="expand_as",
                     inputs={"X": [x], "target_tensor": [target_tensor]},
                     outputs={"Out": [out]})
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="scale", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"scale": float(scale), "bias": float(bias),
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(out, act)


def _elementwise_layer(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return helper.append_activation(out, act)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_pow", x, y, axis, act, name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_mod", x, y, axis, act, name)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return _elementwise_layer("elementwise_floordiv", x, y, axis, act, name)


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="uniform_random", outputs={"Out": [out]},
                     attrs={"shape": list(shape), "dtype": dtype, "min": min,
                            "max": max, "seed": seed})
    return out


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random_batch_size_like", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="uniform_random_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "dtype": dtype, "min": min, "max": max,
               "input_dim_idx": input_dim_idx, "output_dim_idx": output_dim_idx},
    )
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="gaussian_random", outputs={"Out": [out]},
                     attrs={"shape": list(shape), "dtype": dtype, "mean": mean,
                            "std": std, "seed": seed})
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random_batch_size_like", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="gaussian_random_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "dtype": dtype, "mean": mean, "std": std,
               "input_dim_idx": input_dim_idx, "output_dim_idx": output_dim_idx},
    )
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("sampling_id", **locals())
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="sampling_id", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def sum(x):
    helper = LayerHelper("sum", **locals())
    x = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(type="sum", inputs={"X": x}, outputs={"Out": [out]})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="slice", inputs={"Input": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out


def strided_slice(input, axes, starts, ends, strides):
    helper = LayerHelper("strided_slice", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="strided_slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends), "strides": list(strides)})
    return out


def shape(input):
    helper = LayerHelper("shape", **locals())
    out = helper.create_variable_for_type_inference("int32")
    helper.append_op(type="shape", inputs={"Input": [input]}, outputs={"Out": [out]})
    return out


def rank(input):
    from .tensor import fill_constant

    return fill_constant([1], "int32", len(input.shape))


def size(input):
    from .tensor import fill_constant

    return fill_constant([1], "int64", int(np.prod(input.shape)))


def _logical_layer(op_type, x, y=None, out=None, name=None):
    helper = LayerHelper(op_type, name=name)
    if out is None:
        out = helper.create_variable_for_type_inference("bool")
    inputs = {"X": [x]}
    if y is not None:
        inputs["Y"] = [y]
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]})
    return out


def logical_and(x, y, out=None, name=None):
    return _logical_layer("logical_and", x, y, out, name)


def logical_or(x, y, out=None, name=None):
    return _logical_layer("logical_or", x, y, out, name)


def logical_xor(x, y, out=None, name=None):
    return _logical_layer("logical_xor", x, y, out, name)


def logical_not(x, out=None, name=None):
    return _logical_layer("logical_not", x, None, out, name)


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": float(min), "max": float(max)})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="clip_by_norm", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"max_norm": float(max_norm)})
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="maxout", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"groups": groups})
    return out


def space_to_depth(x, blocksize, name=None):
    helper = LayerHelper("space_to_depth", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="space_to_depth", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"blocksize": blocksize})
    return out


def affine_grid(theta, out_shape, name=None):
    helper = LayerHelper("affine_grid", **locals())
    out = helper.create_variable_for_type_inference(theta.dtype)
    inputs = {"Theta": [theta]}
    attrs = {}
    if isinstance(out_shape, Variable):
        inputs["OutputShape"] = [out_shape]
    else:
        attrs["output_shape"] = list(out_shape)
    helper.append_op(type="affine_grid", inputs=inputs, outputs={"Output": [out]},
                     attrs=attrs)
    return out


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None,
                   act=None):
    helper = LayerHelper("affine_channel", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="affine_channel",
                     inputs={"X": [x], "Scale": [scale], "Bias": [bias]},
                     outputs={"Out": [out]})
    return helper.append_activation(out, act)


def hash(input, hash_size, num_hash=1, name=None):
    helper = LayerHelper("hash", **locals())
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="hash", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"mod_by": hash_size, "num_hash": num_hash})
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="grid_sampler", inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="log_loss",
                     inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [out]}, attrs={"epsilon": epsilon})
    return out


def add_position_encoding(input, alpha, beta, name=None):
    helper = LayerHelper("add_position_encoding", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="add_position_encoding", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"alpha": alpha, "beta": beta})
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None, param_attr=None,
                            bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", **locals())
    w = helper.create_parameter(param_attr, [size, x.shape[1], y.shape[1]],
                                _data_type(x))
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [1, size], _data_type(x), is_bias=True)
        inputs["Bias"] = [b]
    helper.append_op(type="bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]})
    return helper.append_activation(out, act)


def shuffle_channel(x, group, name=None):
    helper = LayerHelper("shuffle_channel", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="shuffle_channel", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"group": group})
    return out


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    helper = LayerHelper("temporal_shift", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="temporal_shift", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"seg_num": seg_num, "shift_ratio": shift_ratio})
    return out


def pixel_shuffle(x, upscale_factor):
    helper = LayerHelper("pixel_shuffle", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="pixel_shuffle", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"upscale_factor": upscale_factor})
    return out


def where(condition):
    """Returns indices of true elements — dynamic output; trace-time only."""
    raise NotImplementedError(
        "where(condition) has a dynamic output shape; use layers.cond or "
        "masked arithmetic instead (XLA requires static shapes)"
    )


def sign(x):
    return _unary_layer("sign", x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    helper = LayerHelper("unfold", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="unfold",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={
            "kernel_sizes": kernel_sizes if isinstance(kernel_sizes, list) else [kernel_sizes] * 2,
            "strides": strides if isinstance(strides, list) else [strides] * 2,
            "paddings": paddings if isinstance(paddings, list) else [paddings] * 4,
            "dilations": dilations if isinstance(dilations, list) else [dilations] * 2,
        },
    )
    return out


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    helper = LayerHelper("shard_index", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="shard_index", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"index_num": index_num, "nshards": nshards,
                            "shard_id": shard_id, "ignore_value": ignore_value})
    return out


def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    return _unary_layer("hard_swish", x, name, threshold=threshold, scale=scale,
                        offset=offset)


def unique(x, dtype="int32"):
    helper = LayerHelper("unique", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    index = helper.create_variable_for_type_inference(dtype)
    helper.append_op(type="unique", inputs={"X": [x]},
                     outputs={"Out": [out], "Index": [index]})
    return out, index


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    helper = LayerHelper("global_step_counter")
    name = counter_name or "@STEP_COUNTER@"
    counter = helper.main_program.global_block().create_var(
        name=name, shape=(1,), dtype="int64", persistable=True, stop_gradient=True
    )
    sb = helper.startup_program.global_block()
    sv = sb.create_var(name=name, shape=(1,), dtype="int64", persistable=True)
    from ..initializer import Constant

    Constant(begin - step)(sv, sb)
    helper.append_op(type="increment", inputs={"X": [counter]},
                     outputs={"Out": [counter]}, attrs={"step": float(step)})
    counter.stop_gradient = True
    return counter


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss", **locals())
    diff = helper.create_variable_for_type_inference(x.dtype)
    loss = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(type="smooth_l1_loss", inputs=inputs,
                     outputs={"Diff": [diff], "Out": [loss]},
                     attrs={"sigma": sigma or 1.0})
    return loss


def dice_loss(input, label, epsilon=1e-5):
    from . import tensor as t

    label = one_hot(label, depth=input.shape[-1])
    reduce_dims = list(range(1, len(input.shape)))
    inse = reduce_sum(input * label, dim=reduce_dims)
    dice_denominator = reduce_sum(input, dim=reduce_dims) + reduce_sum(
        label, dim=reduce_dims
    )
    dice_score = 1 - inse * 2 / (dice_denominator + epsilon)
    return reduce_mean(dice_score)


_PYFUNC_TABLE = {}


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-Python op with optional custom backward (reference
    ``operators/py_func_op.cc`` / ``layers/nn.py`` py_func). ``func``
    maps numpy inputs to numpy outputs matching ``out``'s declared
    shapes/dtypes (out vars must carry static shapes — create them with
    ``program.current_block().create_var(...)``); ``backward_func``
    receives (x..., out..., dout...) minus ``skip_vars_in_backward_input``
    and returns grads for each x (None for non-differentiable inputs).
    Lowering: ``jax.pure_callback`` forward wrapped in ``jax.custom_vjp``
    whose backward is a second host callback — the same mechanism the
    distributed_lookup_table lowerings use (ops/distributed_ops.py).
    Callables live in an in-process table keyed by an op attr; a Program
    serialized via proto_io keeps the op but needs the same Python
    process (or re-registration) to execute it — host code cannot ride
    the proto, exactly like the reference's pybind-registered callables."""
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    skip = set(id(v) for v in (skip_vars_in_backward_input or []))
    for o in outs:
        if o.shape is None or any(int(s) < 0 for s in o.shape):
            raise ValueError(
                "py_func out var %r needs a fully static shape" % o.name)
    func_id = len(_PYFUNC_TABLE)
    _PYFUNC_TABLE[func_id] = (
        func, backward_func,
        [id(v) in skip for v in xs],       # skip flags for x slots
        [id(v) in skip for v in outs],     # skip flags for out slots
    )
    helper = LayerHelper("py_func")
    helper.append_op(
        type="py_func",
        inputs={"X": list(xs)},
        outputs={"Out": list(outs)},
        attrs={"func_id": func_id,
               "out_shapes": [[int(s) for s in o.shape] for o in outs],
               "out_dtypes": [str(o.dtype) for o in outs]})
    return out


# -- extra ops used by models ------------------------------------------------

def _register_extra_ops():
    from ..registry import register as reg

    @reg("add_position_encoding")
    def _ape(ctx, op):
        import jax.numpy as jnp

        x = ctx.get_input(op, "X")  # (B, T, D)
        alpha = op.attr("alpha", 1.0)
        beta = op.attr("beta", 1.0)
        b, t, d = x.shape
        half = d // 2
        pos = jnp.arange(t, dtype=x.dtype)[:, None]
        div = jnp.power(10000.0, jnp.arange(half, dtype=x.dtype) / half)
        enc = jnp.concatenate([jnp.sin(pos / div), jnp.cos(pos / div)], axis=1)
        ctx.set_output(op, "Out", alpha * x + beta * enc[None, :, :])

    @reg("hash")
    def _hash(ctx, op):
        import jax.numpy as jnp

        x = ctx.get_input(op, "X").astype(jnp.uint32)
        mod_by = op.attr("mod_by")
        num_hash = op.attr("num_hash", 1)
        outs = []
        for i in range(num_hash):
            h = (x * jnp.uint32(2654435761) + jnp.uint32(i * 97)) % jnp.uint32(mod_by)
            outs.append(h)
        out = jnp.stack(outs, axis=-2) if num_hash > 1 else outs[0]
        ctx.set_output(op, "Out", out.astype(jnp.int64))

    @reg("shard_index")
    def _shard_index(ctx, op):
        import jax.numpy as jnp

        x = ctx.get_input(op, "X")
        index_num = op.attr("index_num")
        nshards = op.attr("nshards")
        shard_id = op.attr("shard_id")
        ignore = op.attr("ignore_value", -1)
        shard_size = (index_num + nshards - 1) // nshards
        in_shard = (x // shard_size) == shard_id
        ctx.set_output(op, "Out", jnp.where(in_shard, x % shard_size, ignore))

    @reg("random_crop", has_state=True)
    def _random_crop(ctx, op):
        import jax

        x = ctx.get_input(op, "X")
        shape = op.attr("shape")
        starts = []
        key = ctx.next_rng()
        keys = jax.random.split(key, len(shape))
        ndim = x.ndim
        offs = []
        for i, target in enumerate(shape):
            dim = ndim - len(shape) + i
            max_off = x.shape[dim] - target
            off = jax.random.randint(keys[i], (), 0, max_off + 1)
            offs.append(off)
        start_indices = [0] * (ndim - len(shape)) + offs
        sizes = list(x.shape[: ndim - len(shape)]) + list(shape)
        out = jax.lax.dynamic_slice(x, start_indices, sizes)
        ctx.set_output(op, "Out", out)


_register_extra_ops()


def linear_chain_crf(input, label, param_attr=None, length=None):
    """CRF negative-log-likelihood layer (reference layers/nn.py
    linear_chain_crf / linear_chain_crf_op.cc). Returns per-sequence
    log-likelihood; transition param rows 0/1 are start/end weights."""
    helper = LayerHelper("linear_chain_crf", **locals())
    num_tags = int(input.shape[-1])
    trans = helper.create_parameter(param_attr, [num_tags + 2, num_tags],
                                    "float32")
    ll = helper.create_variable_for_type_inference("float32")
    alpha = helper.create_variable_for_type_inference("float32")
    eexp = helper.create_variable_for_type_inference("float32")
    texp = helper.create_variable_for_type_inference("float32")
    ll.shape = (-1, 1)
    helper.append_op(
        type="linear_chain_crf",
        inputs={"Emission": [input], "Transition": [trans],
                "Label": [label]},
        outputs={"LogLikelihood": [ll], "Alpha": [alpha],
                 "EmissionExps": [eexp], "TransitionExps": [texp]})
    return ll


def crf_decoding(input, param_attr, label=None, length=None):
    """Viterbi decode with a trained CRF transition (reference
    crf_decoding_op.cc). ``param_attr`` must name the transition param
    created by linear_chain_crf."""
    helper = LayerHelper("crf_decoding", **locals())
    name = param_attr.name if hasattr(param_attr, "name") else str(param_attr)
    block = helper.main_program.global_block()
    if block._find_var_recursive(name) is not None:
        trans = block.var(name)
    else:
        # standalone decode program: declare the named transition param so
        # it resolves from scope (trained by linear_chain_crf elsewhere)
        num_tags = int(input.shape[-1])
        trans = helper.create_parameter(param_attr,
                                        [num_tags + 2, num_tags], "float32")
    path = helper.create_variable_for_type_inference("int64")
    path.shape = (-1, 1)
    path.lod_level = 1
    helper.append_op(
        type="crf_decoding",
        inputs={"Emission": [input], "Transition": [trans]},
        outputs={"ViterbiPath": [path]})
    return path


def ctc_greedy_decoder(input, blank, name=None):
    """Greedy CTC decode: per-step argmax, collapse repeats, drop blanks
    (reference ctc_greedy_decoder = top_k + ctc_align)."""
    helper = LayerHelper("ctc_greedy_decoder", **locals())
    idx = helper.create_variable_for_type_inference("int64")
    idx.shape = (-1, 1)
    idx.lod_level = 1
    helper.append_op(type="arg_max", inputs={"X": [input]},
                     outputs={"Out": [idx]},
                     attrs={"axis": -1, "keepdims": True})
    out = helper.create_variable_for_type_inference("int64")
    out.shape = (-1, 1)
    out.lod_level = 1
    helper.append_op(type="ctc_align", inputs={"Input": [idx]},
                     outputs={"Output": [out]},
                     attrs={"blank": int(blank)})
    return out


def shard_tensor(x, spec, name=None):
    """Annotate an activation with a mesh layout (TPU-native analogue of
    the reference's manual collective placement): ``spec`` is one mesh
    axis name (or None) per dim, e.g. ["dp", None, "sp"] shards batch over
    dp and sequence over sp. Lowering: lax.with_sharding_constraint."""
    helper = LayerHelper("shard_tensor", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(x.shape)
    helper.append_op(type="shard_tensor", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"spec": ["" if s is None else str(s)
                                     for s in spec]})
    return out


def fused_attention(q, k, v, attn_bias=None, scale=None, dropout_prob=0.0,
                    is_test=False, name=None, causal=False,
                    num_kv_heads=None, select=None):
    """Fused softmax(q·kᵀ·scale + bias)·v over [B, H, S, d] heads — a
    single Pallas TPU kernel per (batch, head) with in-kernel dropout;
    falls back to the unfused jnp math off-TPU (kernels/attention.py).
    ``causal`` masks column > row inside the kernel; ``num_kv_heads``
    says K and V carry that many heads, each serving H / num_kv_heads
    consecutive Q heads (grouped-query attention). ``select`` [B, S, S]
    (integer, what ``sparse_index`` gives: nonzero = query t may see key
    s) restricts every head of a batch row to one learned set of keys a
    query; it carries no gradient, goes with neither ``attn_bias`` nor
    dropout, and takes the kernels' select tier (with ``causal``, any S
    that a 128-row tile divides and at most 8 Q heads a K/V head; else
    the masked jnp form). ``v`` may be narrower or wider than ``q`` and
    ``k`` ([B, H, S, dv]; latent attention: 192-wide queries and keys
    against 128-wide values): the result is [B, H, S, dv], the kernels'
    flash tier serves it at every S that a 128-row tile divides (nothing
    is padded), the jnp forms elsewhere."""
    helper = LayerHelper("fused_multihead_attention", **locals())
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if attn_bias is not None:
        inputs["Bias"] = [attn_bias]
    if select is not None:
        inputs["Select"] = [select]
    attrs = {"dropout_prob": float(dropout_prob), "is_test": is_test}
    if scale is not None:
        attrs["scale"] = float(scale)
    if causal:
        attrs["causal"] = True
    if num_kv_heads:
        attrs["num_kv_heads"] = int(num_kv_heads)
    helper.append_op(type="fused_multihead_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def sparse_index(q, k, w, topk, chunk_size=512, name=None):
    """A learned sparse attention's selection (ops/sparse_attention.py):
    from the indexer's queries ``q`` [B, Hi, S, di], its one shared key
    head ``k`` [B, S, di] and its head weights ``w`` [B, S, Hi], the mask
    [B, S, S] int8 of the ``min(t + 1, topk)`` keys ``s <= t`` of largest
    ``sum_j w[t, j] relu(q[t, j] . k[s])`` a query ``t`` (every key tied
    with the ``topk``-th too). No gradient flows through it."""
    helper = LayerHelper("sparse_index", **locals())
    out = helper.create_variable_for_type_inference("int8",
                                                    stop_gradient=True)
    out.shape = (int(q.shape[0]), int(q.shape[2]), int(q.shape[2]))
    helper.append_op(type="sparse_index",
                     inputs={"Q": [q], "K": [k], "W": [w]},
                     outputs={"Select": [out]},
                     attrs={"topk": int(topk), "chunk_size": int(chunk_size)})
    return out


def einsum(equation, *operands, name=None):
    """Tensor contraction by equation (``paddle.einsum`` capability,
    lowered to jnp.einsum — XLA chooses fused layouts, so e.g. attention
    scores contract straight out of the [B, S, H, d] projection layout
    with no materialized transpose)."""
    helper = LayerHelper("einsum", name=name)
    out = helper.create_variable_for_type_inference(operands[0].dtype)
    helper.append_op(type="einsum",
                     inputs={"Operands": list(operands)},
                     outputs={"Out": [out]},
                     attrs={"equation": equation})
    return out


def kv_cache_update(cache, new, cache_len, name=None):
    """Write ``new`` [B, H, T, d] into the KV ring buffer ``cache``
    [B, H, C, d] at per-sequence slot ``cache_len % C``; returns
    ``(updated_cache, cache_len + T)``. A single write must not cross
    the ring boundary (T=1 decode always holds; prefill needs prompt
    length <= C). See kernels/attention.py kv_cache_update."""
    helper = LayerHelper("kv_cache_update", name=name)
    out = helper.create_variable_for_type_inference(cache.dtype)
    out_len = helper.create_variable_for_type_inference("int32")
    helper.append_op(type="kv_cache_update",
                     inputs={"Cache": [cache], "New": [new],
                             "CacheLen": [cache_len]},
                     outputs={"Out": [out], "OutLen": [out_len]})
    return out, out_len


def fused_attention_cache(q, k_cache, v_cache, cache_len, scale=None,
                          name=None):
    """Decode-step attention of q [B, H, Q, d] against a KV ring buffer
    [B, H, C, d] with per-sequence valid lengths ``cache_len`` [B]
    (post-update token counts). Dispatches to the Pallas decode tier at
    large capacities, masked-length fp32 fallback otherwise
    (kernels/attention.py attention_with_cache). Inference-only: no
    gradient."""
    helper = LayerHelper("fused_multihead_attention_cache", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {}
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op(type="fused_multihead_attention_cache",
                     inputs={"Q": [q], "KCache": [k_cache],
                             "VCache": [v_cache], "CacheLen": [cache_len]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def rms_norm(input, epsilon=1e-6, zero_centered=False, param_attr=None,
             name=None):
    """``x * rsqrt(mean(x^2) + eps) * w`` over the last axis. With
    ``zero_centered`` the stored weight is read as ``1 + w`` (and starts
    at 0); else it multiplies as it is (and starts at 1)."""
    from ..initializer import Constant

    helper = LayerHelper("rms_norm", **locals())
    scale = helper.create_parameter(
        param_attr, [int(input.shape[-1])], _data_type(input),
        default_initializer=Constant(0.0 if zero_centered else 1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="rms_norm", inputs={"X": [input], "Scale": [scale]},
                     outputs={"Y": [out]},
                     attrs={"epsilon": float(epsilon),
                            "zero_centered": bool(zero_centered)})
    return out


def swiglu(x, y, name=None):
    """``silu(x) * y``."""
    helper = LayerHelper("swiglu", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="swiglu", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def rotary_embedding(x, rotary_dim=None, theta=10000.0, positions=None,
                     mrope_section=None, name=None):
    """Rotate-half rotary embedding on the first ``rotary_dim`` of the
    head dim of ``x`` [B, H, S, d]. Without ``positions`` row ``s`` sits at
    position ``s``. ``positions`` [B, S] (integer) gives every row its own
    position. ``positions`` [3, B, S] with ``mrope_section`` = three counts
    of frequency pairs adding up to ``rotary_dim / 2`` is the rotary
    embedding in sections of a vision-language model: the first count of
    pairs turns by the first row's positions (time), the next by the
    second's (height), the rest by the third's (width); for text the three
    rows are equal and the result is the plain embedding's, bit for bit."""
    helper = LayerHelper("rotary_embedding", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    attrs = {"rotary_dim": int(rotary_dim or x.shape[-1]),
             "theta": float(theta)}
    if positions is not None:
        inputs["Positions"] = [positions]
    if mrope_section is not None:
        assert positions is not None, "mrope_section needs positions"
        attrs["mrope_section"] = [int(n) for n in mrope_section]
    helper.append_op(type="rotary_embedding", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def causal_conv1d(input, kernel_size, param_attr=None, act=None, name=None):
    """Depthwise causal convolution along axis 1 of ``input`` [B, S, C]:
    one ``kernel_size``-tap filter a channel, no bias; ``act`` is None or
    ``"swish"``, applied inside the op to the float32 sum."""
    helper = LayerHelper("causal_conv1d", **locals())
    w = helper.create_parameter(
        param_attr, [int(input.shape[-1]), int(kernel_size)],
        _data_type(input))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="causal_conv1d",
                     inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]},
                     attrs={"activation": act or ""})
    return out


def gated_short_conv(input, kernel_size, param_attr=None, name=None):
    """LFM2's gated short convolution (ops/linear_attention.py): ``input``
    [B, S, 3h] is the unsplit input projection, channels [B | C | x];
    returns ``C * conv(B * x)`` [B, S, h] through one ``kernel_size``-tap
    depthwise causal filter a channel (the [h, K] parameter), no bias, no
    activation. The three parts are read inside the op, never split out."""
    helper = LayerHelper("gated_short_conv", **locals())
    channels = int(input.shape[-1])
    if channels % 3:
        raise ValueError("gated_short_conv: %d input channels are not "
                         "three equal parts [B | C | x]" % channels)
    w = helper.create_parameter(
        param_attr, [channels // 3, int(kernel_size)], _data_type(input))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gated_short_conv",
                     inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]})
    return out


def gated_delta_rule(q, k, v, a, b, a_log_attr=None, dt_bias_attr=None,
                     chunk_size=64, name=None):
    """The gated delta rule of a linear-attention layer (ops/
    linear_attention.py): q, k [B, S, Hk, dk], v [B, S, Hv, dv], ``b``
    [B, S, Hv] the pre-activation of beta, ``a`` the decay's.

    ``a`` [B, S, Hv]: ONE decay a head and position (Gated DeltaNet);
    creates the per-head ``A_log`` (0: decay rate 1) and ``dt_bias`` (1),
    both [Hv]. ``a`` [B, S, Hv, dk]: a decay a key CHANNEL (Kimi Delta
    Attention; Hk = Hv then); ``A_log`` stays [Hv] and ``dt_bias`` is
    [Hv * dk]. The op tells the two rules apart by ``a``'s rank; each has
    its Pallas kernels where head dims are multiples of 128 and the chunk
    packs into a 128-row group, and its chunked XLA form elsewhere."""
    from ..initializer import Constant

    helper = LayerHelper("gated_delta_rule", **locals())
    heads, dtype = int(v.shape[2]), "float32"
    channels = heads * int(a.shape[3]) if len(a.shape) == 4 else heads
    a_log = helper.create_parameter(a_log_attr, [heads], dtype,
                                    default_initializer=Constant(0.0))
    dt_bias = helper.create_parameter(dt_bias_attr, [channels], dtype,
                                      default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op(
        type="gated_delta_rule",
        inputs={"Q": [q], "K": [k], "V": [v], "A": [a], "B": [b],
                "ALog": [a_log], "DtBias": [dt_bias]},
        outputs={"Out": [out]}, attrs={"chunk_size": int(chunk_size)})
    return out


def moe_route(input, num_experts, k, norm_topk_prob=True, param_attr=None,
              name=None, scoring="softmax", bias_attr=None,
              routed_scaling_factor=None):
    """Router over all ``num_experts`` experts: ``(ids, weights)`` of the
    ``k`` largest of ``softmax(x W)`` (f32), renormalised to sum 1 where
    ``norm_topk_prob``. ``ids`` is int32 [..., k] and carries no
    gradient. ``scoring`` ``"sigmoid"`` scores each expert by itself
    (``sigmoid(x W)``); ``bias_attr`` then creates a ``[num_experts]``
    bias (zero, float32) that is added to the scores for the CHOICE of the
    ``k`` experts only - the weights stay the chosen experts' own scores,
    so it gets no gradient from the loss: a load-balancing rule moves it,
    build it ``trainable=False``. ``routed_scaling_factor`` multiplies the
    (renormalised) weights. Left as they are, the op is the softmax
    router."""
    from ..initializer import Constant

    helper = LayerHelper("moe_route", **locals())
    w = helper.create_parameter(
        param_attr, [int(input.shape[-1]), int(num_experts)], "float32")
    ids = helper.create_variable_for_type_inference("int32",
                                                    stop_gradient=True)
    wts = helper.create_variable_for_type_inference("float32")
    inputs = {"X": [input], "Weight": [w]}
    attrs = {"k": int(k), "norm_topk_prob": bool(norm_topk_prob)}
    if scoring != "softmax":
        assert scoring == "sigmoid", scoring
        attrs["scoring"] = scoring
        if bias_attr is not None:
            inputs["Bias"] = [helper.create_parameter(
                bias_attr, [int(num_experts)], "float32",
                default_initializer=Constant(0.0))]
    if routed_scaling_factor:
        attrs["routed_scaling_factor"] = float(routed_scaling_factor)
    helper.append_op(type="moe_route", inputs=inputs,
                     outputs={"TopkIds": [ids], "TopkWeights": [wts]},
                     attrs=attrs)
    return ids, wts


def moe_experts(input, topk_ids, topk_weights, experts_held, expert_width,
                expert_offset=0, gate_attr=None, up_attr=None, down_attr=None,
                name=None, experts_total=None):
    """The part of a sparse-expert layer's result that the
    ``experts_held`` experts from ``expert_offset`` on give (SwiGLU
    experts of width ``expert_width``, no biases), dropless (ops/
    moe_ops.py). What absent experts would add is left out.
    ``experts_total`` (the router's width) lets the op size its walk's
    chunk to the load its experts hold at even routing: 3/2 of it."""
    helper = LayerHelper("moe_experts", **locals())
    h, E, f = int(input.shape[-1]), int(experts_held), int(expert_width)
    dtype = _data_type(input)
    wg = helper.create_parameter(gate_attr, [E, h, f], dtype)
    wu = helper.create_parameter(up_attr, [E, h, f], dtype)
    wd = helper.create_parameter(down_attr, [E, f, h], dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="moe_experts",
        inputs={"X": [input], "TopkIds": [topk_ids],
                "TopkWeights": [topk_weights], "WGate": [wg], "WUp": [wu],
                "WDown": [wd]},
        outputs={"Out": [out]},
        attrs={"expert_offset": int(expert_offset),
               "experts_total": int(experts_total or 0)})
    return out
