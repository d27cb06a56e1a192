"""AMP program rewrite: insert cast ops around white/black-listed ops.

Parity: reference ``contrib/mixed_precision/fp16_utils.py``
(``rewrite_program``). Parameters stay fp32 (master weights); casts are
in-graph, so the autodiff replay differentiates through them and gradients
arrive fp32. XLA fuses the casts into the surrounding ops — on TPU a
bf16 cast feeding the MXU is free.
"""

import numpy as np

from ... import framework
from ...framework import convert_dtype

__all__ = ["rewrite_program", "cast_model_to_fp16"]

_FLOAT32 = np.dtype("float32")


def _is_float(dtype):
    d = np.dtype(convert_dtype(dtype))
    return np.issubdtype(d, np.floating) or "float" in d.name  # incl. bfloat16


def _is_fp32(var):
    """True when var's dtype normalizes to float32. convert_dtype (not raw
    np.dtype) so a var already rewritten to "bfloat16" doesn't raise."""
    if var is None or var.dtype is None:
        return False
    try:
        return np.dtype(convert_dtype(var.dtype)) == _FLOAT32
    except TypeError:
        return False


def _insert_cast(block, new_ops, cache, name, dest_dtype, suffix):
    """Emit (or reuse) a cast of var `name` to dest_dtype; returns new name."""
    key = (name, suffix)
    if key in cache:
        return cache[key]
    src = block._find_var_recursive(name)
    cast_name = name + suffix
    # stop_gradient must stay False: the autodiff replay cuts grads at
    # stop_gradient vars, and casts sit on the param->loss path
    block.create_var(name=cast_name, shape=list(src.shape),
                     dtype=dest_dtype, persistable=False,
                     stop_gradient=False)
    op = framework.Operator(block, "cast", {"X": [name]},
                            {"Out": [cast_name]},
                            {"out_dtype": np.dtype(dest_dtype).name
                             if np.dtype(dest_dtype).name != "void"
                             else "bfloat16"})
    new_ops.append(op)
    cache[key] = cast_name
    return cast_name


# gray ops whose STATE inputs must never be pulled down to the low
# dtype: batch_norm's running stats feed momentum updates whose
# (1-momentum)*delta terms fall below the bf16 ulp, and its scale/bias
# are optimizer-owned parameters — only the activation X follows the
# low chain (the lowering computes stats and rsqrt in f32 regardless)
_KEEP_FP32_SLOTS = {
    "batch_norm": ("Scale", "Bias", "Mean", "Variance"),
    "layer_norm": ("Scale", "Bias"),
    "rms_norm": ("Scale",),
    "gated_delta_rule": ("ALog", "DtBias"),
    "moe_experts": ("TopkWeights",),
}

# gray ops where only SOME outputs become low-precision: batch_norm's
# MeanOut/VarianceOut alias the f32 running stats and SavedMean/
# SavedVariance stay in the stats dtype — only Y follows X. Ops absent
# from this map mark all float outputs low (the default gray rule).
_LOW_OUTPUT_SLOTS = {
    "batch_norm": ("Y",),
    "layer_norm": ("Y",),
    "rms_norm": ("Y",),
}


def rewrite_program(main_program, amp_lists, dest_dtype="bfloat16"):
    """Walk the forward block: white ops get low-precision inputs, black ops
    get fp32 inputs. Gray ops are untouched (jnp promotion handles mixed
    inputs)."""
    low = convert_dtype(dest_dtype)
    block = main_program.global_block()
    low_suffix = ".cast_" + dest_dtype
    fp32_suffix = ".cast_fp32"
    cache = {}
    new_ops = []
    low_vars = set()  # var names whose produced value is low precision

    for op in list(block.ops):
        if op.type == "autodiff":
            new_ops.append(op)
            continue
        if op.type in amp_lists.white_list and not (
                set(op.input_arg_names()) & amp_lists.black_varnames):
            for slot, names in op.inputs.items():
                casted = []
                for n in names:
                    v = block._find_var_recursive(n)
                    if n not in low_vars and _is_fp32(v):
                        casted.append(_insert_cast(
                            block, new_ops, cache, n, low, low_suffix))
                    else:
                        casted.append(n)
                op.inputs[slot] = casted
            for out in op.output_arg_names():
                v = block._find_var_recursive(out)
                if _is_fp32(v):
                    v.dtype = convert_dtype(dest_dtype)
                    low_vars.add(out)
        elif op.type in amp_lists.black_list:
            for slot, names in op.inputs.items():
                casted = []
                for n in names:
                    if n in low_vars:
                        casted.append(_insert_cast(
                            block, new_ops, cache, n, _FLOAT32, fp32_suffix))
                    else:
                        casted.append(n)
                op.inputs[slot] = casted
        else:
            # gray: if any input is low, pull the remaining fp32 float
            # inputs down too (else jnp promotion silently re-widens the
            # whole chain, e.g. a conv's fp32 bias) and mark outputs low
            if any(n in low_vars for n in op.input_arg_names()):
                keep = _KEEP_FP32_SLOTS.get(op.type, ())
                for slot, names in op.inputs.items():
                    if slot in keep:
                        continue
                    casted = []
                    for n in names:
                        v = block._find_var_recursive(n)
                        if n not in low_vars and _is_fp32(v):
                            casted.append(_insert_cast(
                                block, new_ops, cache, n, low, low_suffix))
                        else:
                            casted.append(n)
                    op.inputs[slot] = casted
                low_slots = _LOW_OUTPUT_SLOTS.get(op.type)
                for slot, names in op.outputs.items():
                    if low_slots is not None and slot not in low_slots:
                        continue
                    for out in names:
                        v = block._find_var_recursive(out)
                        if v is not None and v.dtype is not None and \
                                _is_float(v.dtype):
                            low_vars.add(out)
        new_ops.append(op)
    block.ops = new_ops
    main_program._bump()
    return main_program


def cast_model_to_fp16(program, amp_lists=None, dest_dtype="bfloat16"):
    """Inference-side whole-model cast (reference ``fp16_utils.py``
    ``cast_model_to_fp16``): same rewrite, no backward expected."""
    from .fp16_lists import AutoMixedPrecisionLists

    return rewrite_program(program, amp_lists or AutoMixedPrecisionLists(),
                           dest_dtype)
