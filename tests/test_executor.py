"""Executor: feed/fetch, persistable state, startup init, backward, optimizer
step. Mirrors reference test_executor_and_mul.py / test_optimizer.py."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, optimizer


def test_feed_fetch_mul():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[3], dtype="float32")
        y = layers.data(name="y", shape=[3, 2], dtype="float32", append_batch_size=False)
        out = layers.mul(x, y)
    exe = fluid.Executor()
    xv = np.random.rand(5, 3).astype(np.float32)
    yv = np.random.rand(3, 2).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        (res,) = exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[out])
    np.testing.assert_allclose(res, xv @ yv, rtol=1e-5)


def test_startup_then_train_step_sgd():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        label = layers.data(name="label", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, label))
        opt = optimizer.SGD(learning_rate=0.1)
        opt.minimize(loss)

    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    xv = rng.rand(8, 4).astype(np.float32)
    yv = (xv @ np.array([[1.0], [2.0], [-1.0], [0.5]], np.float32)).astype(np.float32)

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        losses = []
        for _ in range(50):
            (lv,) = exe.run(main, feed={"x": xv, "label": yv}, fetch_list=[loss])
            losses.append(float(lv))
    assert losses[-1] < losses[0] * 0.1, losses[::10]


def test_param_persistence_across_runs():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[2], dtype="float32")
        out = layers.fc(x, size=2, bias_attr=False)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        w_name = main.all_parameters()[0].name
        w0 = np.asarray(fluid.global_scope().find_var(w_name))
        (r1,) = exe.run(main, feed={"x": np.ones((1, 2), np.float32)}, fetch_list=[out])
        (r2,) = exe.run(main, feed={"x": np.ones((1, 2), np.float32)}, fetch_list=[out])
        np.testing.assert_allclose(r1, r2, rtol=1e-6)
        np.testing.assert_allclose(r1.ravel(), w0.sum(axis=0), rtol=1e-5)


def test_backward_grads_match_numeric():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[3], dtype="float32")
        w = layers.create_parameter([3, 1], "float32", name="w")
        out = layers.mul(x, w)
        loss = layers.mean(out)
        grads = fluid.append_backward(loss)
    exe = fluid.Executor()
    xv = np.random.rand(4, 3).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (g,) = exe.run(main, feed={"x": xv}, fetch_list=["w@GRAD"])
    # d(mean(x@w))/dw = mean over batch of x, per column
    expected = xv.mean(axis=0, keepdims=True).T / 1.0
    np.testing.assert_allclose(g, expected, rtol=1e-5)


def test_gradients_api():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[3], dtype="float32")
        y = layers.reduce_sum(layers.square(x))
        (gx,) = fluid.gradients(y, x)
    exe = fluid.Executor()
    xv = np.random.rand(2, 3).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        (g,) = exe.run(main, feed={"x": xv}, fetch_list=[gx])
    np.testing.assert_allclose(g, 2 * xv, rtol=1e-5)


def test_rng_stream_advances():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        u = layers.uniform_random([4], min=0.0, max=1.0)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        (a,) = exe.run(main, fetch_list=[u])
        (b,) = exe.run(main, fetch_list=[u])
    assert not np.allclose(a, b)


def test_dropout_train_vs_test():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[100], dtype="float32")
        d = layers.dropout(x, dropout_prob=0.5, dropout_implementation="upscale_in_train")
    test_prog = main.clone(for_test=True)
    exe = fluid.Executor()
    xv = np.ones((2, 100), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        (train_out,) = exe.run(main, feed={"x": xv}, fetch_list=[d])
        (test_out,) = exe.run(test_prog, feed={"x": xv}, fetch_list=[d.name])
    assert (train_out == 0).any()
    np.testing.assert_allclose(test_out, xv)


def test_py_reader_loop_reference_shape():
    """py_reader (reference layers/io.py): start() -> exe.run without
    feed until core.EOFException; the queue-draining step is DISCARDED
    (state identical before/after EOF), reset() re-arms for epoch 2."""
    B, D = 4, 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        reader = layers.py_reader(capacity=8, shapes=[[B, D], [B, 1]],
                                  dtypes=["float32", "float32"])
        x, y = layers.read_file(reader)
        pred = layers.fc(x, 1, name="pyr_fc")
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    batches = [(rng.rand(B, D).astype(np.float32),
                rng.rand(B, 1).astype(np.float32)) for _ in range(4)]
    reader.decorate_tensor_provider(lambda: iter(batches))
    exe = fluid.Executor()
    scope = fluid.Scope()
    wname = [v.name for v in main.list_vars()
             if v.persistable and ".w_" in v.name][0]
    first_losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for epoch in range(2):
            reader.start()
            steps, losses = 0, []
            while True:
                try:
                    (lv,) = exe.run(main, fetch_list=[loss])
                    losses.append(float(np.asarray(lv).ravel()[0]))
                    steps += 1
                    if steps == len(batches):
                        w_before_eof = np.asarray(
                            scope.find_var(wname)).copy()
                except fluid.core.EOFException:
                    reader.reset()
                    break
            assert steps == len(batches)
            # the EOF (sentinel) step committed nothing
            np.testing.assert_array_equal(
                np.asarray(scope.find_var(wname)), w_before_eof)
            first_losses.append(losses[0])
    # epoch 2 revisits batch 0 with trained weights
    assert first_losses[1] < first_losses[0], first_losses


# -- step-batched execution: exe.run(..., iters=k) ---------------------------

def _sgd_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        label = layers.data(name="label", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, label))
        optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_iters_trajectory_matches_sequential_runs():
    """iters=k with stacked [k, ...] feeds: the per-step loss trajectory
    and final weights match k sequential exe.run calls at 1e-6."""
    main, startup, loss = _sgd_program()
    exe = fluid.Executor()
    rng = np.random.RandomState(7)
    k = 6
    xs = rng.rand(k, 8, 4).astype(np.float32)
    ys = rng.rand(k, 8, 1).astype(np.float32)
    wname = main.all_parameters()[0].name

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        seq = [float(np.asarray(exe.run(
            main, feed={"x": xs[i], "label": ys[i]},
            fetch_list=[loss])[0]).ravel()[0]) for i in range(k)]
        w_seq = np.asarray(fluid.global_scope().find_var(wname))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (traj,) = exe.run(main, feed={"x": xs, "label": ys},
                          fetch_list=[loss], iters=k)
        w_bat = np.asarray(fluid.global_scope().find_var(wname))
    traj = np.asarray(traj).ravel()
    assert traj.shape == (k,)
    np.testing.assert_allclose(traj, seq, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(w_bat, w_seq, atol=1e-6, rtol=1e-6)


def test_iters_invariant_feed_and_single_compile():
    """A per-step-shaped feed is loop-invariant (reused each iteration),
    and a k>1 window compiles exactly ONE executable: the first batched
    run is the only compile-cache miss, repeats are hits."""
    from paddle_tpu.fluid import monitor

    main, startup, loss = _sgd_program()
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype(np.float32),
            "label": rng.rand(8, 1).astype(np.float32)}
    hits = monitor.counter("executor_compile_cache_hit_total")
    misses = monitor.counter("executor_compile_cache_miss_total")
    batched = monitor.counter("executor_batched_run_total")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        m0, h0, b0 = misses.value, hits.value, batched.value
        (t1,) = exe.run(main, feed=feed, fetch_list=[loss], iters=4)
        assert (misses.value - m0, hits.value - h0) == (1, 0)
        (t2,) = exe.run(main, feed=feed, fetch_list=[loss], iters=4)
        assert (misses.value - m0, hits.value - h0) == (1, 1)
        assert batched.value - b0 == 2
    t1 = np.asarray(t1).ravel()
    assert t1.shape == (4,)
    # training on the same batch: the trajectory decreases
    assert t1[-1] < t1[0]
    # the second window starts where the first committed
    assert np.asarray(t2).ravel()[0] < t1[-1]


def test_iters_one_is_the_legacy_path():
    """iters=1 routes through the single-step path byte-for-byte: same
    cache entry as a plain run, and the hook payload is unchanged (no
    'iters' key); batched runs add iters to the record."""
    main, startup, loss = _sgd_program()
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype(np.float32),
            "label": rng.rand(8, 1).astype(np.float32)}
    records = []
    fluid.register_run_hook(records.append)
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            n_entries = len(exe._cache)
            exe.run(main, feed=feed, fetch_list=[loss])
            assert len(exe._cache) == n_entries + 1
            exe.run(main, feed=feed, fetch_list=[loss], iters=1)
            # same cache entry — no new compile
            assert len(exe._cache) == n_entries + 1
            assert records[-1]["cache_hit"] is True
            assert set(records[-1]) == {"program_id", "fetch_names",
                                        "wall_time", "cache_hit",
                                        "profiler_enabled"}
            exe.run(main, feed=feed, fetch_list=[loss], iters=3)
            assert records[-1]["iters"] == 3
            assert records[-1]["cache_hit"] is False
    finally:
        fluid.unregister_run_hook(records.append)
    # one hook firing per run call, batched or not
    assert len(records) == 4


def test_iters_stacked_feed_shape_validation():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        y = layers.data(name="y", shape=[3, 2], dtype="float32",
                        append_batch_size=False)
        out = layers.reduce_sum(y)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(ValueError, match="per-step shape \\[5, 2\\]"):
            exe.run(main, feed={"y": np.zeros((2, 5, 2), np.float32)},
                    fetch_list=[out], iters=2)
        with pytest.raises(ValueError, match="pass either the per-step "
                                             "shape"):
            exe.run(main, feed={"y": np.zeros((7, 2), np.float32)},
                    fetch_list=[out], iters=2)
        with pytest.raises(ValueError, match="iters must be >= 1"):
            exe.run(main, feed={"y": np.zeros((3, 2), np.float32)},
                    fetch_list=[out], iters=0)


def test_iters_requires_committed_state():
    """A program that creates new persistables mid-step (startup-style)
    cannot keep a fixed scan carry — refused with the remedy."""
    main, startup, loss = _sgd_program()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(RuntimeError, match="loop-invariant state"):
            exe.run(startup, iters=2)


def test_iters_py_reader_drains_exactly_k_batches():
    """py_reader-fed batched runs pull exactly k batches up front (in
    order), and a window the pass cannot fill raises EOF with nothing
    committed."""
    B, D = 4, 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        reader = layers.py_reader(capacity=8, shapes=[[B, D]],
                                  dtypes=["float32"])
        x = layers.read_file(reader)
        m = layers.reduce_mean(x)
    batches = [(np.full((B, D), i, np.float32),) for i in range(5)]
    reader.decorate_tensor_provider(lambda: iter(batches))
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        reader.start()
        (t1,) = exe.run(main, fetch_list=[m], iters=2)
        (t2,) = exe.run(main, fetch_list=[m], iters=2)
        np.testing.assert_allclose(np.asarray(t1).ravel(), [0.0, 1.0])
        np.testing.assert_allclose(np.asarray(t2).ravel(), [2.0, 3.0])
        # one batch left < k=2: EOF, pass over
        with pytest.raises(fluid.core.EOFException):
            exe.run(main, fetch_list=[m], iters=2)
        # reset/start re-arms, same contract as the single-step path
        reader.start()
        (t3,) = exe.run(main, fetch_list=[m], iters=2)
        np.testing.assert_allclose(np.asarray(t3).ravel(), [0.0, 1.0])


# -- the host plan: what run() does before any step, single step or window ---

def _reader_program(n_batches, B=4, D=3):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        reader = layers.py_reader(capacity=8, shapes=[[B, D]],
                                  dtypes=["float32"])
        x = layers.read_file(reader)
        w = layers.create_parameter([D], "float32", name="hp_w")
        m = layers.reduce_mean(x * w)
        optimizer.SGD(learning_rate=0.1).minimize(m)
    batches = [(np.full((B, D), i + 1, np.float32),)
               for i in range(n_batches)]
    reader.decorate_tensor_provider(lambda: iter(batches))
    return main, startup, reader, m


@pytest.mark.parametrize("iters", [1, 2])
def test_server_program_serves_or_is_refused(iters, monkeypatch):
    """A server op is a host loop: a single-step run serves it and
    returns [] with nothing compiled; iters=k refuses it."""
    from paddle_tpu.fluid.transpiler import distribute_transpiler

    served = []

    class _Server:
        def serve_forever(self):
            served.append(True)

    monkeypatch.setattr(distribute_transpiler, "build_server_from_attrs",
                        lambda attrs: _Server())
    prog = fluid.Program()
    prog.global_block().append_op("listen_and_serv", inputs={},
                                  outputs={}, attrs={})
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        if iters == 1:
            assert exe.run(prog) == []
            assert served == [True] and not exe._cache
        else:
            with pytest.raises(RuntimeError,
                               match="cannot drive a server program "
                                     "\\(listen_and_serv op\\)"):
                exe.run(prog, iters=iters)
            assert not served


@pytest.mark.parametrize("iters", [1, 2])
def test_save_op_in_a_sub_block_is_refused(iters, tmp_path):
    main, startup, loss = _sgd_program()
    w = main.all_parameters()[0]
    sub = main._create_block()
    sub.append_op("save", inputs={"X": [w]}, outputs={},
                  attrs={"file_path": str(tmp_path / "w")})
    main._rollback()
    exe = fluid.Executor()
    feed = {"x": np.zeros((8, 4), np.float32),
            "label": np.zeros((8, 1), np.float32)}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with pytest.raises(RuntimeError, match="control-flow sub-block"):
            exe.run(main, feed=feed, fetch_list=[loss], iters=iters)
    assert not (tmp_path / "w").exists()


@pytest.mark.parametrize("iters", [1, 2])
def test_garbage_collected_py_reader_is_named(iters):
    import gc

    main, startup, reader, m = _reader_program(2)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        del reader
        gc.collect()
        with pytest.raises(RuntimeError, match="garbage-collected"):
            exe.run(main, fetch_list=[m], iters=iters)


@pytest.mark.parametrize("iters", [1, 2])
def test_py_reader_eof_comes_before_the_step(iters):
    """A queue that cannot fill the step (or the window of k) raises EOF
    with the state untouched and the readers reset for the next pass."""
    main, startup, reader, m = _reader_program(2 * iters - 1)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        reader.start()
        exe.run(main, fetch_list=[m], iters=iters)
        w_before = np.asarray(scope.find_var("hp_w")).copy()
        rng_before = np.asarray(scope.find_var("@rng_state@")).copy()
        # iters=1: the queue is empty; iters=2: one batch is left of two
        with pytest.raises(fluid.core.EOFException):
            exe.run(main, fetch_list=[m], iters=iters)
        np.testing.assert_array_equal(
            np.asarray(scope.find_var("hp_w")), w_before)
        np.testing.assert_array_equal(
            np.asarray(scope.find_var("@rng_state@")), rng_before)
        reader.start()
        (t,) = exe.run(main, fetch_list=[m], iters=iters)
        assert np.asarray(t).size == iters


def test_iters_is_a_member_of_the_one_cache_key():
    """One program at iters=1 and at iters=2: two entries of the one
    cache, each compiled once and hit once."""
    from paddle_tpu.fluid import monitor

    main, startup, loss = _sgd_program()
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype(np.float32),
            "label": rng.rand(8, 1).astype(np.float32)}
    hits = monitor.counter("executor_compile_cache_hit_total")
    misses = monitor.counter("executor_compile_cache_miss_total")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        n, m0, h0 = len(exe._cache), misses.value, hits.value
        for k in (1, 2, 1, 2):
            exe.run(main, feed=feed, fetch_list=[loss], iters=k)
        assert (misses.value - m0, hits.value - h0) == (2, 2)
        assert len(exe._cache) == n + 2
        assert len({len(key) for key in exe._cache}) == 1


def test_iters_gspmd_matches_sequential():
    """iters=k composes with with_data_parallel (GSPMD): trajectory
    matches the sequential CompiledProgram runs."""
    from paddle_tpu.fluid import compiler

    main, startup, loss = _sgd_program()
    exe = fluid.Executor()
    rng = np.random.RandomState(3)
    k = 3
    xs = rng.rand(k, 8, 4).astype(np.float32)
    ys = rng.rand(k, 8, 1).astype(np.float32)
    cp = compiler.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        seq = [float(np.asarray(exe.run(
            cp, feed={"x": xs[i], "label": ys[i]},
            fetch_list=[loss])[0]).ravel()[0]) for i in range(k)]
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (traj,) = exe.run(cp, feed={"x": xs, "label": ys},
                          fetch_list=[loss], iters=k)
    np.testing.assert_allclose(np.asarray(traj).ravel(), seq, atol=1e-6)


def test_save_load_ops_roundtrip(tmp_path):
    """The save/load op pair (reference save_op.cc / load_op.cc): a
    program's save op writes the POST-step value after commit; a second
    program's load op (fluid.layers.load) reads it back as a constant
    of the compiled step."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    path = str(tmp_path / "w.ptc")
    main, st = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, st):
        x = layers.data("slx", [3])
        w = layers.create_parameter(
            [3], "float32",
            default_initializer=fluid.initializer.Constant(2.0))
        y = layers.reduce_sum(layers.elementwise_mul(x, w))
        # append a save op for the PARAM — written after the step runs
        main.current_block().append_op(
            "save", inputs={"X": [w]}, outputs={},
            attrs={"file_path": path})
        fluid.optimizer.SGD(learning_rate=0.1).minimize(y)
    exe = fluid.Executor()
    feed = {"slx": np.ones((1, 3), np.float32)}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(st)
        exe.run(main, feed=feed, fetch_list=[y])
        expect = np.asarray(fluid.global_scope().find_var(w.name))
    # post-step value: 2.0 - 0.1*1 = 1.9
    np.testing.assert_allclose(expect, np.full(3, 1.9, np.float32))

    main2, st2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, st2):
        t = layers.create_tensor("float32")
        layers.load(t, path)
        out = layers.scale(t, scale=10.0)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(st2)
        (r,) = exe.run(main2, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(r), np.full(3, 19.0), rtol=1e-6)
    # missing file fails loudly when the program is lowered (build-time
    # shape inference is best-effort and defers; the run must raise)
    main3, st3 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main3, st3):
        t3 = layers.create_tensor("float32")
        layers.load(t3, str(tmp_path / "absent.ptc"))
        out3 = layers.scale(t3, scale=2.0)
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(Exception, match="does not exist"):
            exe.run(main3, fetch_list=[out3])
