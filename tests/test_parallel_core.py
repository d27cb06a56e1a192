"""paddle_tpu.parallel: ring attention, Ulysses, TP linears, pipeline —
numerics vs single-device references on the 8-device CPU mesh (the
spawn-local-fake-cluster strategy of the reference's TestDistBase, SURVEY §4,
without processes)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_tpu import parallel as pl


@pytest.fixture(scope="module")
def sp_mesh():
    return pl.make_mesh({"sp": 4})


@pytest.fixture(scope="module")
def tp_mesh():
    return pl.make_mesh({"tp": 4})


@pytest.fixture(scope="module")
def pp_mesh():
    return pl.make_mesh({"pp": 4})


def _qkv(b=2, s=32, h=4, d=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_ring_attention_matches_reference(sp_mesh, causal):
    q, k, v = _qkv()
    ref = pl.attention_reference(q, k, v, causal=causal)
    out = pl.ring_attention(q, k, v, sp_mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(sp_mesh, causal):
    q, k, v = _qkv(h=8)
    ref = pl.attention_reference(q, k, v, causal=causal)
    out = pl.ulysses_attention(q, k, v, sp_mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ring_attention_grads(sp_mesh):
    q, k, v = _qkv(b=1, s=16, h=2, d=4)

    def loss_ring(q, k, v):
        return jnp.sum(pl.ring_attention(q, k, v, sp_mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(pl.attention_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_tp_column_then_row_linear(tp_mesh):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    w1 = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    b1 = jnp.asarray(rng.randn(32).astype(np.float32))
    w2 = jnp.asarray(rng.randn(32, 16).astype(np.float32))
    b2 = jnp.asarray(rng.randn(16).astype(np.float32))
    ref = jax.nn.relu(x @ w1 + b1) @ w2 + b2

    def mlp(x, w1, b1, w2, b2):
        h = pl.column_parallel_linear(x, w1, b1)
        h = jax.nn.relu(h)
        return pl.row_parallel_linear(h, w2, b2)

    out = jax.shard_map(
        mlp, mesh=tp_mesh,
        in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None), P()),
        out_specs=P(),
        check_vma=False,
    )(x, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_vocab_parallel_embedding(tp_mesh):
    rng = np.random.RandomState(2)
    table = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, 64, (4, 7)))
    ref = jnp.take(table, ids, axis=0)
    out = jax.shard_map(
        functools.partial(pl.vocab_parallel_embedding),
        mesh=tp_mesh,
        in_specs=(P(), P("tp", None)),
        out_specs=P(),
        check_vma=False,
    )(ids, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_pipeline_matches_sequential(pp_mesh):
    rng = np.random.RandomState(3)
    n_stage, m, bsz, dim = 4, 6, 3, 8
    ws = jnp.asarray(rng.randn(n_stage, dim, dim).astype(np.float32) * 0.3)
    bs = jnp.asarray(rng.randn(n_stage, dim).astype(np.float32) * 0.1)
    mbs = jnp.asarray(rng.randn(m, bsz, dim).astype(np.float32))

    def stage(params, x):
        w, b = params
        return jnp.tanh(x @ w + b)

    ref = mbs
    for i in range(n_stage):
        ref = stage((ws[i], bs[i]), ref)

    out = pl.pipeline(stage, (ws, bs), mbs, pp_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_pipeline_differentiable(pp_mesh):
    rng = np.random.RandomState(4)
    n_stage, m, bsz, dim = 4, 4, 2, 4
    ws = jnp.asarray(rng.randn(n_stage, dim, dim).astype(np.float32) * 0.3)
    bs = jnp.zeros((n_stage, dim), jnp.float32)
    mbs = jnp.asarray(rng.randn(m, bsz, dim).astype(np.float32))

    def stage(params, x):
        w, b = params
        return jnp.tanh(x @ w + b)

    def loss_pl(ws, bs):
        return jnp.sum(pl.pipeline(stage, (ws, bs), mbs, pp_mesh) ** 2)

    def loss_ref(ws, bs):
        y = mbs
        for i in range(n_stage):
            y = stage((ws[i], bs[i]), y)
        return jnp.sum(y ** 2)

    gw_pl, gb_pl = jax.grad(loss_pl, argnums=(0, 1))(ws, bs)
    gw_rf, gb_rf = jax.grad(loss_ref, argnums=(0, 1))(ws, bs)
    np.testing.assert_allclose(np.asarray(gw_pl), np.asarray(gw_rf),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb_pl), np.asarray(gb_rf),
                               rtol=1e-4, atol=1e-4)


def test_parallel_executor_api_trains_dp():
    """fluid.ParallelExecutor (reference parallel_executor.py:28): the
    pre-CompiledProgram multi-device API drives GSPMD DP over the
    8-device mesh; loss decreases and a test-PE shares its weights."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("pe_x", [8], dtype="float32")
        y = layers.data("pe_y", [1], dtype="float32")
        pred = layers.fc(x, 1, name="pe_fc")
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    test_prog = main._prune([loss])

    rng = np.random.RandomState(0)
    xs = rng.rand(16, 8).astype(np.float32)
    ys = (xs.sum(1, keepdims=True) * 0.5).astype(np.float32)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main, scope=scope)
        assert pe.device_count == 8
        losses = [float(np.asarray(pe.run([loss.name],
                                          feed={"pe_x": xs, "pe_y": ys})[0]
                                    ).ravel()[0])
                  for _ in range(6)]
        assert losses[-1] < losses[0], losses
        # share_vars_from: a test PE reads the trained weights
        pe_test = fluid.ParallelExecutor(use_cuda=False,
                                         main_program=test_prog,
                                         share_vars_from=pe)
        (lv,) = pe_test.run([loss.name], feed={"pe_x": xs, "pe_y": ys})
        np.testing.assert_allclose(float(np.asarray(lv).ravel()[0]),
                                   losses[-1], rtol=0.2)


def test_parallel_executor_per_device_feed_and_guards():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("pd_x", [4], dtype="float32")
        s = layers.reduce_sum(x)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        pe = fluid.ParallelExecutor(main_program=main, scope=scope)
        # reference-style per-device feed: list of dicts concatenates
        halves = [{"pd_x": np.ones((2, 4), np.float32)},
                  {"pd_x": np.full((2, 4), 2.0, np.float32)}]
        (sv,) = pe.run([s.name], feed=halves)
        np.testing.assert_allclose(float(np.asarray(sv).ravel()[0]), 24.0)
    # the refusal must point at the working multi-process path (fleet
    # collective / paddle_tpu.distributed — whose single-vs-multi
    # equivalence tests/test_fleet_collective.py pins)
    with pytest.raises(ValueError, match="fleet collective"):
        fluid.ParallelExecutor(main_program=main, num_trainers=4)
