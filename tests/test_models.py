"""Model zoo smoke + convergence tests on tiny shapes (the reference's
"book"/dist model suite scaled down — SURVEY §4 end-to-end tests)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import dygraph, optimizer
from paddle_tpu.models import bert, deepfm, resnet, transformer


def _run_steps(main, startup, feed_fn, fetch, n=4):
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        vals = []
        for i in range(n):
            out = exe.run(main, feed=feed_fn(i), fetch_list=fetch)
            vals.append(np.asarray(out[0]))
        return vals


def test_resnet18_train_step():
    main, startup, loss, acc = resnet.build_train_program(
        depth=18, num_classes=10, image_size=32, lr=0.01)
    rng = np.random.RandomState(0)
    imgs = rng.rand(8, 3, 32, 32).astype(np.float32)
    labels = rng.randint(0, 10, (8, 1)).astype(np.int64)
    losses = _run_steps(main, startup,
                        lambda i: {"img": imgs, "label": labels}, [loss], n=6)
    assert all(np.isfinite(l).all() for l in losses)
    assert losses[-1] < losses[0]  # memorizes the fixed batch


@pytest.mark.slow
def test_resnet50_builds_and_runs():
    main, startup, loss, acc = resnet.build_train_program(
        depth=50, num_classes=10, image_size=32)
    rng = np.random.RandomState(0)
    imgs = rng.rand(2, 3, 32, 32).astype(np.float32)
    labels = rng.randint(0, 10, (2, 1)).astype(np.int64)
    losses = _run_steps(main, startup,
                        lambda i: {"img": imgs, "label": labels}, [loss], n=1)
    assert np.isfinite(losses[0]).all()


def test_bert_tiny_mlm_loss_decreases():
    cfg = bert.BertConfig.tiny()
    main, startup, loss = bert.build_pretrain_program(cfg, seq_len=32,
                                                      lr=1e-3)
    batch = bert.synthetic_batch(cfg, 4, 32)
    losses = _run_steps(main, startup, lambda i: batch, [loss], n=6)
    assert all(np.isfinite(l).all() for l in losses)
    assert losses[-1] < losses[0]


def test_deepfm_tiny_train():
    cfg = deepfm.DeepFMConfig.tiny()
    main, startup, loss, pred = deepfm.build_train_program(cfg, lr=1e-2)
    batch = deepfm.synthetic_batch(cfg, 16)
    losses = _run_steps(main, startup, lambda i: batch, [loss], n=8)
    assert all(np.isfinite(l).all() for l in losses)
    assert losses[-1] < losses[0]


def test_transformer_tiny_dygraph_train():
    with dygraph.guard():
        model = transformer.Transformer.tiny()
        opt = optimizer.Adam(learning_rate=1e-3)
        src, tgt, labels, pos = transformer.synthetic_batch(512, 512, 2, 16)
        bias = dygraph.to_variable(transformer.make_causal_bias(16))
        losses = []
        for _ in range(4):
            logits = model(dygraph.to_variable(src), dygraph.to_variable(tgt),
                           dygraph.to_variable(pos), dygraph.to_variable(pos),
                           bias)
            loss = transformer.loss_fn(logits, dygraph.to_variable(labels))
            model.clear_gradients()
            opt.minimize(loss, parameter_list=model.parameters())
            losses.append(float(np.asarray(loss.numpy())))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]


def test_transformer_jit_trace_matches_eager():
    with dygraph.guard():
        model = transformer.Transformer.tiny()
        model.eval()
        src, tgt, labels, pos = transformer.synthetic_batch(512, 512, 2, 16)
        bias = transformer.make_causal_bias(16)
        args = [dygraph.to_variable(v) for v in (src, tgt, pos, pos, bias)]
        eager_out = model(*args).numpy()
        outs, traced = dygraph.jit.trace(model, args)
    static_out = traced([src, tgt, pos, pos, bias])
    np.testing.assert_allclose(np.asarray(static_out[0]), eager_out,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_resnet_nhwc_matches_nchw():
    """data_format="NHWC" runs the SAME math as NCHW (feed contract
    unchanged — one transpose at graph entry): losses agree to float
    tolerance over steps (reduce orders may differ per layout). On v5e
    the two compile to identical step times (XLA layout assignment
    normalizes)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(4, 3, 32, 32).astype("float32"),
            "label": rng.randint(0, 10, (4, 1)).astype("int64")}
    out = {}
    for fmt in ("NCHW", "NHWC"):
        main, st, loss, acc = resnet.build_train_program(
            depth=18, num_classes=10, image_size=32, seed=3,
            data_format=fmt)
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(st)
            out[fmt] = [float(np.asarray(
                exe.run(main, feed=feed, fetch_list=[loss])[0]).ravel()[0])
                for _ in range(3)]
    np.testing.assert_allclose(out["NCHW"], out["NHWC"], rtol=2e-4)
