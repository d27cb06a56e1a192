"""ResNet for ImageNet-style classification — BASELINE.json `configs`
entry 2.

Parity: reference ``tests/unittests/dist_se_resnext.py`` /
``tests/book/test_image_classification.py`` model family; built from the
same fluid layer surface (conv2d/batch_norm/pool2d/fc).

TPU note: ``data_format`` selects the activation layout END TO END.
"NCHW" is the reference default; "NHWC" runs the convs in the layout
the v5e tiles natively (channels on lanes) — the feed contract stays
NCHW and one transpose at graph entry converts.
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, optimizer

_DEPTH_CFG = {
    18: ("basic", [2, 2, 2, 2]),
    34: ("basic", [3, 4, 6, 3]),
    50: ("bottleneck", [3, 4, 6, 3]),
    101: ("bottleneck", [3, 4, 23, 3]),
    152: ("bottleneck", [3, 8, 36, 3]),
}


def _conv_bn(x, filters, ksize, stride=1, act=None, name=None, fmt="NCHW"):
    conv = layers.conv2d(
        x, num_filters=filters, filter_size=ksize, stride=stride,
        padding=(ksize - 1) // 2, bias_attr=False, data_format=fmt,
        param_attr=fluid.ParamAttr(name=name + "_w") if name else None)
    return layers.batch_norm(conv, act=act, data_layout=fmt)


def _shortcut(x, filters, stride, fmt):
    in_c = x.shape[-1] if fmt == "NHWC" else x.shape[1]
    if in_c != filters or stride != 1:
        return _conv_bn(x, filters, 1, stride, fmt=fmt)
    return x


def _basic_block(x, filters, stride, fmt):
    y = _conv_bn(x, filters, 3, stride, act="relu", fmt=fmt)
    y = _conv_bn(y, filters, 3, 1, fmt=fmt)
    return layers.relu(
        layers.elementwise_add(y, _shortcut(x, filters, stride, fmt)))


def _bottleneck_block(x, filters, stride, fmt):
    y = _conv_bn(x, filters, 1, act="relu", fmt=fmt)
    y = _conv_bn(y, filters, 3, stride, act="relu", fmt=fmt)
    y = _conv_bn(y, filters * 4, 1, fmt=fmt)
    return layers.relu(
        layers.elementwise_add(y, _shortcut(x, filters * 4, stride, fmt)))


def resnet_forward(img, label=None, depth=50, num_classes=1000,
                   data_format="NCHW"):
    kind, blocks = _DEPTH_CFG[depth]
    block_fn = _basic_block if kind == "basic" else _bottleneck_block
    fmt = data_format

    x = img
    if fmt == "NHWC":
        x = layers.transpose(x, [0, 2, 3, 1])   # feed contract stays NCHW
    x = _conv_bn(x, 64, 7, stride=2, act="relu", fmt=fmt)
    x = layers.pool2d(x, pool_size=3, pool_stride=2, pool_padding=1,
                      pool_type="max", data_format=fmt)
    for stage, n in enumerate(blocks):
        filters = 64 * (2 ** stage)
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            x = block_fn(x, filters, stride, fmt)
    x = layers.pool2d(x, pool_type="avg", global_pooling=True,
                      data_format=fmt)
    logits = layers.fc(x, size=num_classes)
    if label is None:
        return logits, None, None
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    acc = layers.accuracy(layers.softmax(logits), label)
    return logits, loss, acc


def build_train_program(depth=50, num_classes=1000, image_size=224,
                        lr=0.1, momentum=0.9, seed=7, use_amp=False,
                        data_format="NCHW"):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[3, image_size, image_size],
                          dtype="float32")
        label = layers.data(name="label", shape=[1], dtype="int64")
        _, loss, acc = resnet_forward(img, label, depth, num_classes,
                                      data_format=data_format)
        opt = optimizer.Momentum(
            learning_rate=lr, momentum=momentum,
            regularization=fluid.regularizer.L2Decay(1e-4))
        if use_amp:
            from ..fluid.contrib import mixed_precision

            opt = mixed_precision.decorate(opt)
        opt.minimize(loss)
    return main, startup, loss, acc


def build_infer_program(depth=50, num_classes=1000, image_size=224, seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[3, image_size, image_size],
                          dtype="float32")
        logits, _, _ = resnet_forward(img, None, depth, num_classes)
    return main, startup, logits
