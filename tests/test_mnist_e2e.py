"""End-to-end LeNet/MNIST training — the reference "book" suite milestone
(``tests/book/test_recognize_digits.py``), entry 1 of BASELINE.json's `configs`.

Uses synthetic class-separable data (zero-egress environment)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, optimizer


def lenet(img, label):
    conv1 = layers.conv2d(img, num_filters=6, filter_size=5, padding=2, act="relu")
    pool1 = layers.pool2d(conv1, pool_size=2, pool_stride=2)
    conv2 = layers.conv2d(pool1, num_filters=16, filter_size=5, act="relu")
    pool2 = layers.pool2d(conv2, pool_size=2, pool_stride=2)
    fc1 = layers.fc(pool2, size=120, act="relu")
    fc2 = layers.fc(fc1, size=84, act="relu")
    logits = layers.fc(fc2, size=10)
    loss = layers.mean(
        layers.softmax_with_cross_entropy(logits, label)
    )
    acc = layers.accuracy(layers.softmax(logits), label)
    return loss, acc


def synthetic_digits(rng, n):
    """Class-separable 28x28 images: digit k = bright kth row band."""
    labels = rng.randint(0, 10, (n, 1)).astype(np.int64)
    imgs = rng.rand(n, 1, 28, 28).astype(np.float32) * 0.1
    for i, k in enumerate(labels.ravel()):
        imgs[i, 0, k * 2 : k * 2 + 3, :] += 1.0
    return imgs, labels


def test_mnist_lenet_train():
    main = fluid.Program()
    startup = fluid.Program()
    main.random_seed = 7
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[1, 28, 28], dtype="float32")
        label = layers.data(name="label", shape=[1], dtype="int64")
        loss, acc = lenet(img, label)
        opt = optimizer.Adam(learning_rate=1e-3)
        opt.minimize(loss)

    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        first = last = None
        for step in range(40):
            imgs, labels = synthetic_digits(rng, 32)
            lv, av = exe.run(main, feed={"img": imgs, "label": labels},
                             fetch_list=[loss, acc])
            if first is None:
                first = float(lv)
            last, last_acc = float(lv), float(av)
        assert last < first * 0.5, (first, last)
        assert last_acc > 0.8, last_acc

    # inference program path
    test_prog = main.clone(for_test=True)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        imgs, labels = synthetic_digits(rng, 16)
        (lv,) = exe.run(test_prog, feed={"img": imgs, "label": labels},
                        fetch_list=[loss.name])
        assert np.isfinite(lv)


def test_save_load_roundtrip(tmp_path):
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        out = layers.fc(x, size=2)
    exe = fluid.Executor()
    xv = np.random.rand(3, 4).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (r1,) = exe.run(main, feed={"x": xv}, fetch_list=[out])
        fluid.io.save_persistables(exe, str(tmp_path), main)
    with fluid.scope_guard(fluid.Scope()):
        fluid.io.load_persistables(exe, str(tmp_path), main)
        (r2,) = exe.run(main, feed={"x": xv}, fetch_list=[out])
    np.testing.assert_allclose(r1, r2, rtol=1e-6)


def test_save_load_inference_model(tmp_path):
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        hidden = layers.fc(x, size=8, act="relu")
        out = layers.fc(hidden, size=2, act="softmax")
        label = layers.data(name="label", shape=[1], dtype="int64")
        loss = layers.mean(layers.cross_entropy(out, label))
        # clone for eval BEFORE adding optimizer ops (reference idiom)
        test_prog = main.clone(for_test=True)
        optimizer.SGD(0.01).minimize(loss)
    exe = fluid.Executor()
    xv = np.random.rand(3, 4).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": xv, "label": np.zeros((3, 1), np.int64)},
                fetch_list=[loss])  # one train step
        (r1,) = exe.run(test_prog,
                        feed={"x": xv, "label": np.zeros((3, 1), np.int64)},
                        fetch_list=[out.name])
        fluid.io.save_inference_model(str(tmp_path), ["x"], [out], exe, main)
    with fluid.scope_guard(fluid.Scope()):
        prog, feed_names, fetch_vars = fluid.io.load_inference_model(str(tmp_path), exe)
        assert feed_names == ["x"]
        # pruned program has no optimizer/loss ops
        types = [op.type for op in prog.global_block().ops]
        assert "sgd" not in types and "autodiff" not in types
        (r2,) = exe.run(prog, feed={"x": xv}, fetch_list=fetch_vars)
    np.testing.assert_allclose(r1, r2, rtol=1e-5)
