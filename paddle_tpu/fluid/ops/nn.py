"""NN ops: conv, pooling, normalization, softmax, dropout, interpolation.

Parity: reference ``operators/conv_op.cc``, ``pool_op.cc``,
``batch_norm_op.cc``, ``layer_norm_op.cc``, ``group_norm_op.cc``,
``instance_norm_op.cc``, ``softmax_op.cc``, ``dropout_op.cc``,
``interpolate_op.cc``, ``conv_transpose_op.cc``, ``lrn_op.cc``,
``data_norm_op.cc``, ``spectral_norm_op.cc``, ``grid_sampler``/``affine_*``.

Data layout is NCHW (fluid default); XLA:TPU relayouts internally to feed the
MXU for convs, so no manual layout transform is needed. Convs and matmuls
stay whole — XLA tiles them; elementwise epilogues (bias, act) fuse.
"""


import numpy as np

from ..registry import register


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


@register("conv2d")
@register("depthwise_conv2d")
def _conv2d(ctx, op):
    import jax

    x = ctx.get_input(op, "Input")  # NCHW or NHWC (data_format attr)
    w = ctx.get_input(op, "Filter")  # OIHW either way
    strides = _pair(op.attr("strides", [1, 1]))
    pads = _pair(op.attr("paddings", [0, 0]))
    dil = _pair(op.attr("dilations", [1, 1]))
    groups = op.attr("groups", 1) or 1
    fmt = op.attr("data_format", "NCHW")
    if op.type == "depthwise_conv2d":
        groups = x.shape[-1] if fmt == "NHWC" else x.shape[1]
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=((pads[0], pads[0]), (pads[1], pads[1])),
        rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=(fmt, "OIHW", fmt),
    )
    ctx.set_output(op, "Output", out)


@register("conv3d")
def _conv3d(ctx, op):
    import jax

    x = ctx.get_input(op, "Input")  # NCDHW
    w = ctx.get_input(op, "Filter")  # OIDHW
    strides = op.attr("strides", [1, 1, 1])
    pads = op.attr("paddings", [0, 0, 0])
    dil = op.attr("dilations", [1, 1, 1])
    groups = op.attr("groups", 1) or 1
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=tuple(strides),
        padding=tuple((p, p) for p in pads),
        rhs_dilation=tuple(dil),
        feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )
    ctx.set_output(op, "Output", out)


def _deconv(x, w, strides, pads, dils, groups):
    """Fractionally-strided conv (reference conv_transpose semantics:
    out = (H-1)*s + k_eff - 2p): a conv over the lhs-dilated input with a
    spatially FLIPPED kernel. Fluid deconv filters are [C_in, C_out/g,
    *k]; the equivalent forward conv wants [C_out, C_in/g, *k]."""
    import jax

    nd = len(strides)
    cin = w.shape[0]
    cog = w.shape[1]  # C_out / groups
    # [g, C_in/g, C_out/g, *k] -> [g, C_out/g, C_in/g, *k] -> flat OI*k
    wg = w.reshape((groups, cin // groups, cog) + w.shape[2:])
    wg = wg.swapaxes(1, 2).reshape((groups * cog, cin // groups) +
                                   w.shape[2:])
    flip = (slice(None), slice(None)) + (slice(None, None, -1),) * nd
    wg = wg[flip]
    k_eff = [(w.shape[2 + i] - 1) * dils[i] + 1 for i in range(nd)]
    pad = [(k_eff[i] - 1 - pads[i], k_eff[i] - 1 - pads[i])
           for i in range(nd)]
    spatial = "DHW"[-nd:]
    spec = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    return jax.lax.conv_general_dilated(
        x, wg, window_strides=(1,) * nd, padding=pad,
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dils),
        dimension_numbers=spec, feature_group_count=groups)


@register("conv2d_transpose")
def _conv2d_transpose(ctx, op):
    x = ctx.get_input(op, "Input")
    w = ctx.get_input(op, "Filter")  # IOHW in fluid transpose convs
    strides = _pair(op.attr("strides", [1, 1]))
    pads = _pair(op.attr("paddings", [0, 0]))
    dil = _pair(op.attr("dilations", [1, 1]))
    groups = op.attr("groups", 1) or 1
    ctx.set_output(op, "Output", _deconv(x, w, strides, pads, dil, groups))


@register("conv3d_transpose")
def _conv3d_transpose(ctx, op):
    x = ctx.get_input(op, "Input")
    w = ctx.get_input(op, "Filter")
    strides = tuple(op.attr("strides", [1, 1, 1]))
    pads = list(op.attr("paddings", [0, 0, 0]))
    dil = tuple(op.attr("dilations", [1, 1, 1]))
    groups = op.attr("groups", 1) or 1
    ctx.set_output(op, "Output", _deconv(x, w, strides, pads, dil, groups))


def _pool(x, pooling_type, ksize, strides, pads, ceil_mode, exclusive,
          global_pool, adaptive, data_format="NCHW"):
    import jax
    import jax.numpy as jnp

    nhwc = data_format == "NHWC"
    h, w = (x.shape[1], x.shape[2]) if nhwc else (x.shape[2], x.shape[3])
    if global_pool:
        ksize = (h, w)
        strides = (1, 1)
        pads = (0, 0)
    if adaptive:
        # adaptive pooling: output ksize[i] bins; use reduce over equal splits
        oh, ow = ksize
        assert h % oh == 0 and w % ow == 0, "adaptive pool needs divisible dims"
        kh, kw = h // oh, w // ow
        ksize, strides, pads = (kh, kw), (kh, kw), (0, 0)
    ph, pw = (pads[0], pads[0]), (pads[1], pads[1])
    if ceil_mode:
        # add extra (stride-1) padding on the high side so partial windows count
        ph = (pads[0], pads[0] + strides[0] - 1)
        pw = (pads[1], pads[1] + strides[1] - 1)
    if nhwc:
        window = (1,) + tuple(ksize) + (1,)
        strides_full = (1,) + tuple(strides) + (1,)
        pad_full = ((0, 0), ph, pw, (0, 0))
    else:
        window = (1, 1) + tuple(ksize)
        strides_full = (1, 1) + tuple(strides)
        pad_full = ((0, 0), (0, 0), ph, pw)
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides_full, pad_full)
    # avg
    ones = jnp.ones_like(x)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_full, pad_full)
    if exclusive or ceil_mode:
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides_full, pad_full)
        return summed / counts
    return summed / (ksize[0] * ksize[1])


@register("pool2d")
def _pool2d(ctx, op):
    x = ctx.get_input(op, "X")
    out = _pool(
        x,
        op.attr("pooling_type", "max"),
        _pair(op.attr("ksize", [2, 2])),
        _pair(op.attr("strides", [1, 1])),
        _pair(op.attr("paddings", [0, 0])),
        op.attr("ceil_mode", False),
        op.attr("exclusive", True),
        op.attr("global_pooling", False),
        op.attr("adaptive", False),
        op.attr("data_format", "NCHW"),
    )
    ctx.set_output(op, "Out", out)


@register("pool3d")
def _pool3d(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    ksize = tuple(op.attr("ksize", [2, 2, 2]))
    strides = tuple(op.attr("strides", [1, 1, 1]))
    pads = op.attr("paddings", [0, 0, 0])
    ptype = op.attr("pooling_type", "max")
    if op.attr("global_pooling", False):
        ksize = x.shape[2:]
        strides = (1, 1, 1)
        pads = [0, 0, 0]
    window = (1, 1) + ksize
    strides_full = (1, 1) + strides
    pad_full = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    if ptype == "max":
        out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides_full, pad_full)
    else:
        out = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_full, pad_full) / int(
            np.prod(ksize)
        )
    ctx.set_output(op, "Out", out)


@register("softmax")
def _softmax(ctx, op):
    import jax

    x = ctx.get_input(op, "X")
    axis = op.attr("axis", -1)
    ctx.set_output(op, "Out", jax.nn.softmax(x, axis=axis))


@register("log_softmax")
def _log_softmax(ctx, op):
    import jax

    x = ctx.get_input(op, "X")
    ctx.set_output(op, "Out", jax.nn.log_softmax(x, axis=op.attr("axis", -1)))


@register("dropout", has_state=True)
def _dropout(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    p = op.attr("dropout_prob", 0.5)
    is_test = op.attr("is_test", False)
    impl = op.attr("dropout_implementation", "downgrade_in_infer")
    # Masks come from 8-bit random words, applied multiplicatively. Against
    # bernoulli (32-bit uniform) + where this is 4x less generator traffic
    # and fuses into one VPU pass — measured on v5e BERT-base AMP:
    # 94.8 -> 87.5 ms/step. Keep-probability resolution is 1/256;
    # INFERENCE scales by the EXACT 1-p (reference-checkpoint parity,
    # ADVICE r3 #3) and the realized-keep (thresh/256) correction folds
    # into the TRAIN-time factor, so E[train out] == E[test out] still
    # holds exactly.
    keep = 1.0 - p
    thresh = min(max(int(round(keep * 256.0)), 0 if keep <= 0.0 else 1), 256)
    if is_test:
        out = x * keep if impl == "downgrade_in_infer" else x
        ctx.set_output(op, "Out", out)
        return
    if thresh <= 0 or thresh >= 256:
        # degenerate keep (rounds to 0 or 1): constant output, but the op
        # still consumes its key so the autodiff replay stream and any
        # key-count-sensitive config comparison stay aligned
        ctx.next_rng()
        one_or_zero = (jnp.ones_like if thresh >= 256 else jnp.zeros_like)
        if thresh >= 256:
            # keep-everything grid cell: the downgrade impl must still
            # carry the exact keep so E[train] == x*keep == E[test]
            full = x * keep if impl == "downgrade_in_infer" else x
        else:
            full = jnp.zeros_like(x)
        ctx.set_output(op, "Out", full)
        ctx.set_output(op, "Mask", one_or_zero(x))
        return
    bits = jax.random.bits(ctx.next_rng(), x.shape, jnp.uint8)
    mask = bits < jnp.uint8(thresh)
    realized = thresh / 256.0
    if impl == "upscale_in_train":
        scale = 1.0 / realized             # E[out] == x; infer passes x
    else:
        scale = keep / realized            # E[out] == x*keep == infer
    out = x * (mask.astype(x.dtype) * scale)
    ctx.set_output(op, "Out", out)
    ctx.set_output(op, "Mask", mask.astype(x.dtype))


@register("batch_norm")
def _batch_norm(ctx, op):
    """Training mode computes batch stats and updates running stats
    (persistable writes, committed by the executor); test mode uses running
    stats. Reference ``operators/batch_norm_op.cc``."""
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    mean = ctx.get_input(op, "Mean")
    var = ctx.get_input(op, "Variance")
    eps = op.attr("epsilon", 1e-5)
    momentum = op.attr("momentum", 0.9)
    is_test = op.attr("is_test", False)
    layout = op.attr("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]

    if is_test or op.attr("use_global_stats", False):
        use_mean, use_var = mean, var
    else:
        # SINGLE-pass stats (jnp.var re-derives the mean — a second
        # full-activation sweep): E[x-a] and E[(x-a)^2]
        # reduce over the same input in one fused sweep, f32
        # accumulation, SHIFTED by the running mean as anchor — exact
        # algebraically (var = E[(x-a)^2] - E[x-a]^2), and the
        # cancellation error scales with |batch_mean - running_mean|
        # instead of |mean|, vanishing as training settles.
        # Early-training caveat (anchor = fresh running mean = 0): the
        # f32 relative error of use_var is ~(1 + mc^2/var) * 2^-24, so
        # losing even half the mantissa needs |batch_mean - anchor| >
        # ~64*sigma — orders beyond any real pre-BN activation (std-init
        # convs give |mc| ~ 0.01*sigma). The max(., 0) clamp plus eps in
        # rsqrt bound the fallout if it ever triggers; the off-anchor
        # regime is pinned by test_batch_norm_far_anchor_stats.
        anchor = mean.astype(jnp.float32).reshape(bshape)
        xc = x.astype(jnp.float32) - anchor
        mc, m2 = jnp.mean(xc, axis=axes), jnp.mean(xc * xc, axis=axes)
        use_var = jnp.maximum(m2 - mc * mc, 0.0)
        use_mean = mc + anchor.reshape(-1)
        new_mean = momentum * mean + (1.0 - momentum) * use_mean
        new_var = momentum * var + (1.0 - momentum) * use_var
        # MeanOut/VarianceOut alias Mean/Variance in the reference;
        # running stats keep their declared dtype
        for slot, val, ref in (("MeanOut", new_mean, mean),
                               ("VarianceOut", new_var, var)):
            names = op.output(slot)
            if names:
                ctx.set(names[0], val.astype(ref.dtype))
        ctx.set_output(op, "SavedMean", use_mean.astype(mean.dtype))
        ctx.set_output(op, "SavedVariance",
                       (1.0 / jnp.sqrt(use_var + eps)).astype(mean.dtype))

    inv = 1.0 / jnp.sqrt(use_var.astype(jnp.float32) + eps)
    # normalize in x's dtype (reference keeps Y in the input precision;
    # a low-precision program must not silently promote downstream)
    alpha = (inv * scale.astype(jnp.float32)).astype(x.dtype)
    beta = bias.astype(x.dtype)
    out = ((x - use_mean.astype(x.dtype).reshape(bshape))
           * alpha.reshape(bshape) + beta.reshape(bshape))
    ctx.set_output(op, "Y", out)


@register("layer_norm")
def _layer_norm(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = op.attr("epsilon", 1e-5)
    begin = op.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    # two-pass (x - mean)^2 form: measured FASTER than the single-pass
    # E[x^2] variant on BERT-base (189k vs 177k tok/s — the single-pass
    # rewrite cost more than the fused second reduce) and numerically
    # stabler per-row; batch_norm differs (see there). Under AMP the op
    # is GRAY: x arrives bf16, stats and normalize run in f32 (the
    # converts fuse into the reduces), and Y casts back to x's dtype —
    # per-row bf16 stats over 768 elements would be too coarse.
    xf = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) / jnp.sqrt(var + eps)
    norm_shape = x.shape[begin:]
    if scale is not None:
        out = out * scale.astype(jnp.float32).reshape(norm_shape)
    if bias is not None:
        out = out + bias.astype(jnp.float32).reshape(norm_shape)
    ctx.set_output(op, "Y", out.astype(x.dtype))
    # stats keep their DECLARED dtype (f32 under AMP where X is bf16 but
    # the stat vars stay f32; the input dtype in all-bf16 programs) —
    # same convention as batch_norm's SavedMean/SavedVariance
    for slot, val in (("Mean", mean), ("Variance", var)):
        names = op.output(slot)
        if names:
            ctx.set(names[0], jnp.reshape(val, (-1,)).astype(
                ctx.var_dtype(names[0])))


@register("rms_norm")
def _rms_norm(ctx, op):
    """``x * rsqrt(mean(x^2) + eps) * w`` over the last axis, ``w`` read as
    ``1 + Scale`` where ``zero_centered`` (a weight stored round 0).
    Statistics and the normalize run in f32 whatever X's dtype (gray
    under AMP, like layer_norm); Y is cast back to X's dtype."""
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    scale = ctx.get_input(op, "Scale")
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        + op.attr("epsilon", 1e-6))
    w = scale.astype(jnp.float32)
    out = out * (1.0 + w if op.attr("zero_centered", False) else w)
    ctx.set_output(op, "Y", out.astype(x.dtype))


@register("swiglu")
def _swiglu(ctx, op):
    """``silu(X) * Y``: the gate of a gated FFN (and of a gated norm)."""
    import jax

    x = ctx.get_input(op, "X")
    y = ctx.get_input(op, "Y")
    ctx.set_output(op, "Out", jax.nn.silu(x) * y)


@register("rotary_embedding")
def _rotary_embedding(ctx, op):
    """Rotate-half rotary position embedding on the first ``rotary_dim``
    of X's last axis (partial rotary: the rest passes through). X is
    [B, H, S, d]. Without ``Positions`` the position of row ``s`` is
    ``s``. ``Positions`` [B, S] gives each row's own; ``Positions``
    [3, B, S] with ``mrope_section`` (three counts of frequency pairs that
    add up to ``rotary_dim / 2``) gives the first count of pairs the first
    row's positions, the next the second's, the rest the third's (text:
    three equal rows, which is the plain embedding). Angles in f32, Out in
    X's dtype."""
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    S, d = x.shape[2], x.shape[3]
    rd = int(op.attr("rotary_dim", d))
    assert rd % 2 == 0 and rd <= d, (rd, d)
    inv = 1.0 / (float(op.attr("theta", 10000.0))
                 ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    pos = ctx.get_input(op, "Positions")
    sections = op.attr("mrope_section", None)
    if pos is None:
        ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[None, None]
    else:
        ang = pos.astype(jnp.float32)[..., None] * inv   # [(3,) B, S, rd/2]
        if sections:
            assert pos.ndim == 3 and pos.shape[0] == 3 and \
                sum(sections) == rd // 2, (pos.shape, sections, rd)
            pair = jnp.arange(rd // 2)
            ang = jnp.where(pair < sections[0], ang[0], jnp.where(
                pair < sections[0] + sections[1], ang[1], ang[2]))
        assert ang.ndim == 3, (pos.shape, sections)
        ang = ang[:, None]                               # [B, 1, S, rd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :rd // 2], xf[..., rd // 2:rd]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xf[..., rd:]], axis=-1)
    ctx.set_output(op, "Out", out.astype(x.dtype))


@register("group_norm")
def _group_norm(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # NCHW
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = op.attr("epsilon", 1e-5)
    groups = op.attr("groups")
    n, c = x.shape[:2]
    gx = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, gx.ndim))
    mean = jnp.mean(gx, axis=axes, keepdims=True)
    var = jnp.var(gx, axis=axes, keepdims=True)
    out = ((gx - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        out = out * scale.reshape(bshape)
    if bias is not None:
        out = out + bias.reshape(bshape)
    ctx.set_output(op, "Y", out)
    ctx.set_output(op, "Mean", jnp.reshape(mean, (n, groups)))
    ctx.set_output(op, "Variance", jnp.reshape(var, (n, groups)))


@register("instance_norm")
def _instance_norm(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # NCHW
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    eps = op.attr("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) / jnp.sqrt(var + eps)
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if scale is not None:
        out = out * scale.reshape(bshape)
    if bias is not None:
        out = out + bias.reshape(bshape)
    ctx.set_output(op, "Y", out)


@register("data_norm")
def _data_norm(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    size = ctx.get_input(op, "BatchSize")
    total = ctx.get_input(op, "BatchSum")
    sq = ctx.get_input(op, "BatchSquareSum")
    mean = total / size
    scale = jnp.sqrt(size / sq)
    ctx.set_output(op, "Y", (x - mean) * scale)
    ctx.set_output(op, "Means", mean)
    ctx.set_output(op, "Scales", scale)


@register("spectral_norm")
def _spectral_norm(ctx, op):
    import jax.numpy as jnp

    w = ctx.get_input(op, "Weight")
    u = ctx.get_input(op, "U")
    v = ctx.get_input(op, "V")
    dim = op.attr("dim", 0)
    power_iters = op.attr("power_iters", 1)
    eps = op.attr("eps", 1e-12)
    wmat = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
    for _ in range(max(power_iters, 0)):
        v = wmat.T @ u
        v = v / (jnp.linalg.norm(v) + eps)
        u = wmat @ v
        u = u / (jnp.linalg.norm(u) + eps)
    sigma = u @ wmat @ v
    ctx.set_output(op, "Out", w / sigma)


@register("lrn")
def _lrn(ctx, op):
    import jax
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # NCHW
    n_size = op.attr("n", 5)
    k = op.attr("k", 2.0)
    alpha = op.attr("alpha", 1e-4)
    beta = op.attr("beta", 0.75)
    sq = jnp.square(x)
    half = n_size // 2
    summed = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, (1, n_size, 1, 1), (1, 1, 1, 1),
        ((0, 0), (half, n_size - 1 - half), (0, 0), (0, 0)),
    )
    ctx.set_output(op, "Out", x / jnp.power(k + alpha * summed, beta))


def _resize(x, out_h, out_w, method, align_corners):
    import jax

    n, c, h, w = x.shape
    return jax.image.resize(
        x, (n, c, out_h, out_w), method=method
    )


def _interp_out_hw(ctx, op, x):
    out_h = op.attr("out_h", -1)
    out_w = op.attr("out_w", -1)
    scale = op.attr("scale", 0.0)
    if op.input("OutSize"):
        sz = np.asarray(ctx.get_input(op, "OutSize"))
        out_h, out_w = int(sz[0]), int(sz[1])
    elif scale and scale > 0:
        out_h, out_w = int(x.shape[2] * scale), int(x.shape[3] * scale)
    return out_h, out_w


@register("bilinear_interp")
def _bilinear_interp(ctx, op):
    x = ctx.get_input(op, "X")
    out_h, out_w = _interp_out_hw(ctx, op, x)
    ctx.set_output(op, "Out", _resize(x, out_h, out_w, "bilinear", op.attr("align_corners", True)))


@register("nearest_interp")
def _nearest_interp(ctx, op):
    x = ctx.get_input(op, "X")
    out_h, out_w = _interp_out_hw(ctx, op, x)
    ctx.set_output(op, "Out", _resize(x, out_h, out_w, "nearest", op.attr("align_corners", True)))


@register("trilinear_interp")
def _trilinear_interp(ctx, op):
    import jax

    x = ctx.get_input(op, "X")  # NCDHW
    out_d = op.attr("out_d", -1)
    out_h = op.attr("out_h", -1)
    out_w = op.attr("out_w", -1)
    n, c = x.shape[:2]
    ctx.set_output(op, "Out", jax.image.resize(x, (n, c, out_d, out_h, out_w), "trilinear"))


@register("affine_channel")
def _affine_channel(ctx, op):
    x = ctx.get_input(op, "X")  # NCHW
    scale = ctx.get_input(op, "Scale")
    bias = ctx.get_input(op, "Bias")
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    ctx.set_output(op, "Out", x * scale.reshape(bshape) + bias.reshape(bshape))


@register("temporal_shift")
def _temporal_shift(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")
    seg_num = op.attr("seg_num")
    ratio = op.attr("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // seg_num
    x5 = x.reshape(n, seg_num, c, h, w)
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    back = jnp.concatenate([x5[:, 1:, :c1], jnp.zeros_like(x5[:, :1, :c1])], axis=1)
    fwd = jnp.concatenate([jnp.zeros_like(x5[:, :1, c1:c2]), x5[:, :-1, c1:c2]], axis=1)
    keep = x5[:, :, c2:]
    out = jnp.concatenate([back, fwd, keep], axis=2)
    ctx.set_output(op, "Out", out.reshape(nt, c, h, w))


@register("grid_sampler")
def _grid_sampler(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # NCHW
    grid = ctx.get_input(op, "Grid")  # NHW2 in [-1,1]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    x1, y1 = x0 + 1, y0 + 1
    wx1, wy1 = gx - x0, gy - y0
    wx0, wy0 = 1.0 - wx1, 1.0 - wy1

    def sample(xi, yi):
        xi = jnp.clip(xi, 0, w - 1).astype(np.dtype("int32"))
        yi = jnp.clip(yi, 0, h - 1).astype(np.dtype("int32"))
        batch = jnp.arange(n)[:, None, None]
        return x[batch, :, yi, xi]  # N,H,W,C

    out = (
        sample(x0, y0) * (wx0 * wy0)[..., None]
        + sample(x1, y0) * (wx1 * wy0)[..., None]
        + sample(x0, y1) * (wx0 * wy1)[..., None]
        + sample(x1, y1) * (wx1 * wy1)[..., None]
    )
    ctx.set_output(op, "Output", jnp.moveaxis(out, -1, 1))


@register("affine_grid")
def _affine_grid(ctx, op):
    import jax.numpy as jnp

    theta = ctx.get_input(op, "Theta")  # N,2,3
    shape = op.attr("output_shape")
    if op.input("OutputShape"):
        shape = [int(v) for v in np.asarray(ctx.get_input(op, "OutputShape"))]
    n, c, h, w = shape
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gx, gy = jnp.meshgrid(xs, ys)
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1).reshape(1, h * w, 3)
    out = jnp.einsum("bhk,bok->bho", jnp.tile(base, (theta.shape[0], 1, 1)), theta)
    ctx.set_output(op, "Output", out.reshape(theta.shape[0], h, w, 2))


@register("im2sequence")
def _im2sequence(ctx, op):
    import jax

    x = ctx.get_input(op, "X")
    ksizes = op.attr("kernels")
    strides = op.attr("strides", [1, 1])
    pads = op.attr("paddings", [0, 0, 0, 0])
    patches = jax.lax.conv_general_dilated_patches(
        x, tuple(ksizes), tuple(strides), ((pads[0], pads[2]), (pads[1], pads[3]))
    )
    n, ckk, oh, ow = patches.shape
    ctx.set_output(op, "Out", patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, ckk))


@register("row_conv")
def _row_conv(ctx, op):
    import jax.numpy as jnp

    x = ctx.get_input(op, "X")  # (B, T, D) batched path
    w = ctx.get_input(op, "Filter")  # (future_len, D)
    flen = w.shape[0]
    t = x.shape[-2]
    out = jnp.zeros_like(x)
    for k in range(flen):
        shifted = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, k), (0, 0)])[..., k:k + t, :]
        out = out + shifted * w[k]
    ctx.set_output(op, "Out", out)


@register("multiplex")
def _multiplex(ctx, op):
    import jax.numpy as jnp

    ids = ctx.get_input(op, "Ids")
    xs = jnp.stack(ctx.get_inputs(op, "X"), axis=0)
    idx = ids.reshape(-1).astype(np.dtype("int32"))
    rows = jnp.arange(idx.shape[0])
    ctx.set_output(op, "Out", xs[idx, rows])


@register("fused_multihead_attention", has_state=True)
def _fused_multihead_attention(ctx, op):
    """One-kernel attention (paddle_tpu/kernels/attention.py) — the
    in-framework form of the reference's multihead_matmul fusion
    (``ir/multihead_matmul_fuse_pass.cc``), available in training too."""
    from ...kernels.attention import fused_attention

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    bias = ctx.get_input(op, "Bias")
    p = float(op.attr("dropout_prob", 0.0))
    is_test = bool(op.attr("is_test", False))
    scale = op.attr("scale", None)
    drop = 0.0 if is_test else p
    key = ctx.next_rng() if drop > 0.0 else None
    kv_heads = int(op.attr("num_kv_heads", 0) or 0)
    select = ctx.get_input(op, "Select")
    if kv_heads and kv_heads != q.shape[1]:
        # grouped-query attention: K/V arrive [B, Hkv, S, d] and each KV
        # head serves H/Hkv consecutive Q heads; repeated to the Q head
        # count before the kernel (autodiff sums the copies' gradients),
        # but for the select tier, which takes K/V at their own head count
        assert k.shape[1] == kv_heads and q.shape[1] % kv_heads == 0, (
            q.shape, k.shape, kv_heads)
        if select is None:
            import jax.numpy as jnp

            k = jnp.repeat(k, q.shape[1] // kv_heads, axis=1)
            v = jnp.repeat(v, q.shape[1] // kv_heads, axis=1)
    ctx.set_output(op, "Out", fused_attention(
        q, k, v, bias, scale=scale, dropout_prob=drop, rng_key=key,
        causal=bool(op.attr("causal", False)), select=select))


@register("fused_multihead_attention_packed", has_state=True)
def _fused_multihead_attention_packed(ctx, op):
    """Packed-layout ([B, S, H*d]) variant: heads strided inside the
    kernel, no [B, H, S, d] transposes in the graph
    (kernels/attention.py packed tier)."""
    from ...kernels.attention import fused_attention_packed

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    bias = ctx.get_input(op, "Bias")
    p = float(op.attr("dropout_prob", 0.0))
    is_test = bool(op.attr("is_test", False))
    scale = op.attr("scale", None)
    n_heads = int(op.attr("n_heads", 1))
    drop = 0.0 if is_test else p
    key = ctx.next_rng() if drop > 0.0 else None
    ctx.set_output(op, "Out", fused_attention_packed(
        q, k, v, bias, n_heads=n_heads, scale=scale, dropout_prob=drop,
        rng_key=key))


@register("sequence_parallel_attention", has_state=True)
def _sequence_parallel_attention(ctx, op):
    """Long-context attention with the sequence dim sharded over the
    strategy mesh's "sp" axis (kernels/attention.py: ring KV rotation or
    Ulysses all-to-all, picked per the ``strategy`` attr / auto rule).
    Packed [B, S, H*d] in and out; with no mesh (or no "sp" axis) the
    same math runs single-shard, so programs are portable."""
    from ...kernels.attention import sequence_parallel_attention

    q = ctx.get_input(op, "Q")
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    bias = ctx.get_input(op, "Bias")
    p = float(op.attr("dropout_prob", 0.0))
    is_test = bool(op.attr("is_test", False))
    drop = 0.0 if is_test else p
    key = ctx.next_rng() if drop > 0.0 else None
    ctx.set_output(op, "Out", sequence_parallel_attention(
        q, k, v, int(op.attr("n_heads", 1)), bias=bias,
        mesh=getattr(ctx, "mesh", None),    # eager ctx carries no mesh
        seq_axis=str(op.attr("seq_axis", "sp")),
        batch_axis=str(op.attr("batch_axis", "dp")),
        causal=bool(op.attr("causal", False)),
        scale=op.attr("scale", None), dropout_prob=drop, rng_key=key,
        strategy=str(op.attr("strategy", "auto"))))


@register("kv_cache_update")
def _kv_cache_update(ctx, op):
    """Ring-buffer KV cache write (kernels/attention.py): New [B, H, T, d]
    lands at slot CacheLen % C of Cache [B, H, C, d]; OutLen = CacheLen
    + T so decode programs carry the token count on-device (no host
    round-trip between steps)."""
    from ...kernels.attention import kv_cache_update

    cache = ctx.get_input(op, "Cache")
    new = ctx.get_input(op, "New")
    cache_len = ctx.get_input(op, "CacheLen")
    out, out_len = kv_cache_update(cache, new, cache_len)
    ctx.set_output(op, "Out", out)
    ctx.set_output(op, "OutLen", out_len)


@register("fused_multihead_attention_cache")
def _fused_multihead_attention_cache(ctx, op):
    """Decode-step attention against a KV ring buffer
    (kernels/attention.py attention_with_cache): masked-length fallback
    or the Pallas decode tier at large capacities. Inference-only.
    ``causal_window`` (default off — old programs deserialize unchanged)
    is the speculative-verify form: Q rows are the last Q tokens
    written, each masking the columns written after it."""
    from ...kernels.attention import attention_with_cache

    q = ctx.get_input(op, "Q")
    k_cache = ctx.get_input(op, "KCache")
    v_cache = ctx.get_input(op, "VCache")
    cache_len = ctx.get_input(op, "CacheLen")
    scale = op.attr("scale", None)
    ctx.set_output(op, "Out", attention_with_cache(
        q, k_cache, v_cache, cache_len, scale=scale,
        causal_window=bool(op.attr("causal_window", False))))


@register("paged_kv_cache_update")
def _paged_kv_cache_update(ctx, op):
    """Block-granular KV cache write (kernels/attention.py): New
    [B, H, T, d] scatters through PageTable [B, npages] into the shared
    Pool [P, H, ptok, d] at the slot's logical ring positions; OutLen =
    CacheLen + T. The paged generalization of ``kv_cache_update`` —
    writes may cross page and ring boundaries."""
    from ...kernels.attention import paged_kv_cache_update

    pool = ctx.get_input(op, "Pool")
    new = ctx.get_input(op, "New")
    table = ctx.get_input(op, "PageTable")
    cache_len = ctx.get_input(op, "CacheLen")
    out, out_len = paged_kv_cache_update(pool, new, table, cache_len)
    ctx.set_output(op, "Out", out)
    ctx.set_output(op, "OutLen", out_len)


@register("paged_multihead_attention_cache")
def _paged_multihead_attention_cache(ctx, op):
    """Decode-step attention against a PAGED KV cache
    (kernels/attention.py paged_attention_cache): gather-dense fallback
    or the Pallas paged tier (SMEM page table via scalar prefetch) at
    large capacities. Inference-only."""
    from ...kernels.attention import paged_attention_cache

    q = ctx.get_input(op, "Q")
    k_pool = ctx.get_input(op, "KPool")
    v_pool = ctx.get_input(op, "VPool")
    table = ctx.get_input(op, "PageTable")
    cache_len = ctx.get_input(op, "CacheLen")
    ctx.set_output(op, "Out", paged_attention_cache(
        q, k_pool, v_pool, table, cache_len,
        scale=op.attr("scale", None)))
