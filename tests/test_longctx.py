"""Long-context tier (kernels/attention.py sequence-parallel section):
ring attention (KV rotation over ICI neighbors, online-softmax fold
across hops) and DeepSpeed-Ulysses (all-to-all head<->sequence swap) over
the 'sp' mesh axis, plus the recompute memory knob and the
sequence-sharded decode session. Numerics are pinned against the
single-device oracle — the SAME op with n=1, and the jnp reference —
including the causal and dropout paths; dropout masks are keyed on
GLOBAL (batch, head, tile) coordinates, so the sharded outputs must be
bit-compatible with the unsharded ones, not just statistically alike."""

import os

import numpy as np
import pytest

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu.kernels.attention as A
from paddle_tpu.fluid import monitor
from paddle_tpu.kernels.attention import sequence_parallel_attention

pytestmark = pytest.mark.longctx

RTOL, ATOL = 2e-5, 2e-5
B, H, S, D = 2, 4, 256, 16
RNG = np.random.RandomState(5)
Q3 = (RNG.randn(B, S, H * D) * 0.5).astype(np.float32)
K3 = (RNG.randn(B, S, H * D) * 0.5).astype(np.float32)
V3 = (RNG.randn(B, S, H * D) * 0.5).astype(np.float32)
BIAS = np.zeros((B, 1, 1, S), np.float32)
BIAS[0, 0, 0, -17:] = -1e4
SCALE = 1.0 / np.sqrt(D)


def _mesh(n_sp, n_dp=1):
    devs = np.array(jax.devices()[:n_dp * n_sp]).reshape(n_dp, n_sp)
    return Mesh(devs, ("dp", "sp"))


def _split(x3):
    x = x3.reshape(B, S, H, D)
    return jnp.asarray(np.transpose(x, (0, 2, 1, 3)))


def _oracle(bias, causal, p_drop=0.0, rng_key=None):
    """The op itself at n=1 — fixes the dropout masks AND the math."""
    return sequence_parallel_attention(
        jnp.asarray(Q3), jnp.asarray(K3), jnp.asarray(V3), H,
        bias=None if bias is None else jnp.asarray(bias), mesh=None,
        causal=causal, dropout_prob=p_drop, rng_key=rng_key)


def _run(strategy, n_sp, bias, causal, p_drop=0.0, rng_key=None, n_dp=1):
    return sequence_parallel_attention(
        jnp.asarray(Q3), jnp.asarray(K3), jnp.asarray(V3), H,
        bias=None if bias is None else jnp.asarray(bias),
        mesh=_mesh(n_sp, n_dp), causal=causal, dropout_prob=p_drop,
        rng_key=rng_key, strategy=strategy)


# -- dispatch: the strategy argument, and auto's rule ----------------------
class TestStrategyDispatch:
    def test_auto_picks_ring_when_axis_does_not_divide_heads(self):
        # H=4 and n=8: auto cannot split the heads, so it routes to ring
        # — observable as ring hops on the counter
        hops = monitor.counter("attn_ring_hops_total")
        before = hops.value
        _run("auto", 8, None, False)
        assert hops.value == before + 7    # n - 1 hops per ring pass

    def test_forced_ulysses_rejects_indivisible_heads(self):
        with pytest.raises(ValueError, match="divide"):
            _run("ulysses", 3, None, False)   # H=4, n=3


# -- numerics: sharded vs single-device ----------------------------------
@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_matches_reference_no_dropout(strategy, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", _split(Q3), _split(K3)) * SCALE
    s = s + jnp.asarray(BIAS)
    if causal:
        rows = jnp.arange(S)[:, None]
        s = jnp.where((jnp.arange(S)[None, :] <= rows)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref4 = jnp.einsum("bhqk,bhkd->bhqd", p, _split(V3))
    ref = np.transpose(np.asarray(ref4), (0, 2, 1, 3)).reshape(B, S, H * D)
    got = np.asarray(_run(strategy, 4, BIAS, causal))
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sharded_matches_single_device_with_dropout(strategy, causal):
    """The pinned-closeness claim on the dropout path: the n=1 op run is
    the oracle (same global tile-keyed masks), the 4-shard run must
    reproduce it."""
    key = jax.random.PRNGKey(42)
    ref = np.asarray(_oracle(BIAS, causal, 0.2, key))
    got = np.asarray(_run(strategy, 4, BIAS, causal, 0.2, key))
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_gradients_match_single_device(strategy):
    key = jax.random.PRNGKey(7)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out * out)
        return jax.grad(f, argnums=(0, 1, 2))

    ref_fn = lambda q, k, v: sequence_parallel_attention(
        q, k, v, H, bias=jnp.asarray(BIAS), mesh=None, causal=True,
        dropout_prob=0.2, rng_key=key)
    got_fn = lambda q, k, v: sequence_parallel_attention(
        q, k, v, H, bias=jnp.asarray(BIAS), mesh=_mesh(4), causal=True,
        dropout_prob=0.2, rng_key=key, strategy=strategy)
    gr = loss(ref_fn)(jnp.asarray(Q3), jnp.asarray(K3), jnp.asarray(V3))
    gg = loss(got_fn)(jnp.asarray(Q3), jnp.asarray(K3), jnp.asarray(V3))
    for r, g in zip(gr, gg):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-5, atol=5e-5)


def test_batch_axis_composes():
    """dp=2 x sp=2: batch shards over 'dp' while the sequence shards
    over 'sp'; dropout masks keyed on GLOBAL batch ids keep the result
    identical to the unsharded run."""
    key = jax.random.PRNGKey(9)
    ref = np.asarray(_oracle(BIAS, True, 0.2, key))
    got = np.asarray(_run("ring", 2, BIAS, True, 0.2, key, n_dp=2))
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL)


def test_seq_not_divisible_raises():
    with pytest.raises(ValueError, match="divisible"):
        _run("ring", 3, None, False)    # S=256, n=3


def test_dropout_chunk_tile_guard():
    q = jnp.zeros((1, 128, H * D), jnp.float32)
    with pytest.raises(ValueError, match="tile"):
        sequence_parallel_attention(
            q, q, q, H, mesh=_mesh(4), dropout_prob=0.1,
            rng_key=jax.random.PRNGKey(0))    # S/n = 32 < 64-wide tile


# -- model layer: train step + recompute + decode session ----------------
def _trace_tiny(seq_parallel, strategy="auto", V=64, Bm=4, Sm=32,
                drop=0.0, seed=7):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.models import transformer

    with dygraph.guard():
        model = transformer.Transformer(
            V, V, d_model=32, n_heads=4, d_inner=64, n_layers=2,
            max_len=max(64, Sm), dropout_rate=drop,
            seq_parallel=seq_parallel, attn_strategy=strategy)
        rng = np.random.RandomState(seed)
        for _, p in model.named_parameters():
            p.set_value(rng.uniform(-0.1, 0.1, p.shape).astype(np.float32))
        src, tgt, labels, pos = transformer.synthetic_batch(V, V, Bm, Sm)
        bias = transformer.make_causal_bias(Sm)
        args = [dygraph.to_variable(v)
                for v in (src, tgt, pos, pos, bias)]
        _, tl = dygraph.jit.trace(model, args)
    return model, tl, (src, tgt, pos, bias, labels)


def _train_losses(model, tl, data, V=64, Sm=32, compiledfn=None,
                  recompute=False, steps=3):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, optimizer
    from paddle_tpu.fluid.executor import scope_guard

    src, tgt, pos, bias, labels = data
    startup = fluid.Program()
    with fluid.program_guard(tl.program, startup):
        logits = tl.program.global_block().var(tl._fetch_names[0])
        label = layers.data("lc_label", [Sm, 1], dtype="int64")
        ce = layers.softmax_with_cross_entropy(
            layers.reshape(logits, [-1, V]),
            layers.reshape(label, [-1, 1]))
        loss = layers.mean(ce)
        opt = optimizer.SGD(learning_rate=0.1)
        if recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(model.checkpoint_vars(tl.program))
        opt.minimize(loss)
    tl._materialize_scope()
    exe = fluid.Executor()
    prog = tl.program
    if compiledfn:
        prog = compiledfn(fluid.CompiledProgram(prog))
    feed = dict(zip(tl._feed_names, (src, tgt, pos, pos, bias)))
    feed["lc_label"] = labels
    losses = []
    with scope_guard(tl._scope):
        exe.run(startup)
        for _ in range(steps):
            (lv,) = exe.run(prog, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
    return losses, tl, feed, loss.name


_SP_MESH = lambda cp: cp.with_data_parallel(
    mesh_axes=("dp", "sp"), mesh_shape={"dp": 2, "sp": 4}, places=8)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_train_step_matches_single_device(strategy):
    """Full traced train step (loss + SGD) on a dp=2 x sp=4 mesh vs the
    plain single-device program — loss trajectories pinned to fp32
    closeness."""
    m0, tl0, data = _trace_tiny(False)
    ref, _, _, _ = _train_losses(m0, tl0, data)
    m1, tl1, _ = _trace_tiny(True, strategy)
    got, _, _, _ = _train_losses(m1, tl1, data, compiledfn=_SP_MESH)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_recompute_lowers_peak_memory_same_losses():
    """RecomputeOptimizer over the per-block checkpoint vars: the loss
    trajectory must be unchanged while the statically-estimated peak
    live bytes drop (the activations live only inside remat segments)."""
    from paddle_tpu.utils import liveness

    m0, tl0, data = _trace_tiny(True, "ring", Sm=64)
    base, tl0, feed0, l0 = _train_losses(m0, tl0, data, Sm=64)
    m1, tl1, _ = _trace_tiny(True, "ring", Sm=64)
    rec, tl1, feed1, l1 = _train_losses(m1, tl1, data, Sm=64,
                                        recompute=True)
    np.testing.assert_allclose(rec, base, rtol=1e-5, atol=1e-6)
    p0 = liveness.program_peak_bytes(tl0.program, feed0, tl0._scope, [l0])
    p1 = liveness.program_peak_bytes(tl1.program, feed1, tl1._scope, [l1])
    assert p1 < p0, "recompute did not lower peak live bytes: %d >= %d" \
        % (p1, p0)


@pytest.mark.decode
def test_seq_sharded_decode_token_identical():
    """seq_shards=4 decode session (KV ring caches + cross K/V sharded
    on the sequence dim over 'sp') vs the unsharded session — token
    stream and finished mask identical, INCLUDING a generation that
    wraps the ring capacity (prompt 8 + 12 new > capacity 16)."""
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.models import transformer

    V, Bd, SRC, PROMPT, CAP = 64, 2, 16, 8, 16
    rng = np.random.RandomState(3)
    src = rng.randint(2, V, (Bd, SRC)).astype(np.int64)
    prompt = rng.randint(2, V, (Bd, PROMPT)).astype(np.int64)
    plens = np.array([PROMPT, PROMPT - 2], np.int64)

    def gen(seq_shards):
        with dygraph.guard():
            model = transformer.Transformer.tiny(V, V)
            prng = np.random.RandomState(11)
            for _, p in model.named_parameters():
                p.set_value(prng.uniform(-0.3, 0.3,
                                         p.shape).astype(np.float32))
            sess = transformer.build_decode_session(
                model, Bd, SRC, PROMPT, CAP, end_id=1,
                seq_shards=seq_shards)
        return sess.generate(src, prompt, plens, 12)

    toks1, fin1 = gen(1)
    toks4, fin4 = gen(4)
    assert np.array_equal(toks1, toks4)
    assert np.array_equal(fin1, fin4)


def test_seq_shards_validates_divisibility():
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.models import transformer

    with dygraph.guard():
        model = transformer.Transformer.tiny()
        with pytest.raises(ValueError, match="divide"):
            transformer.build_decode_session(model, 1, 10, 8, 18,
                                             seq_shards=4)


# -- heavy: S >= 1024 over the full 8-device ring ------------------------
@pytest.mark.slow
@pytest.mark.parametrize("strategy,n", [("ring", 8), ("ulysses", 4)])
def test_long_sequence_8_shards(strategy, n):
    # ulysses needs n | H (H=4); ring takes the full 8-device axis
    S_big = 1024
    rng = np.random.RandomState(13)
    q = (rng.randn(1, S_big, H * D) * 0.5).astype(np.float32)
    k = (rng.randn(1, S_big, H * D) * 0.5).astype(np.float32)
    v = (rng.randn(1, S_big, H * D) * 0.5).astype(np.float32)
    devs = np.array(jax.devices()[:n]).reshape(1, n)
    mesh = Mesh(devs, ("dp", "sp"))
    ref = np.asarray(sequence_parallel_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), H, mesh=None,
        causal=True))
    got = np.asarray(sequence_parallel_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), H, mesh=mesh,
        causal=True, strategy=strategy))
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=1e-4)
