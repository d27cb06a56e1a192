"""Pallas kernels (kernels/attention.py) — run through the pallas
interpreter on CPU so the real kernel bodies execute in CI; numerics are
checked against the jnp reference path and fp64 truth."""

import os

import numpy as np
import pytest

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.attention import (_ref_attention, _supports_pallas,
                                          fused_attention)

RNG = np.random.RandomState(3)
B, H, S, D = 2, 3, 16, 8
SCALE = 1.0 / np.sqrt(D)
Q = (RNG.randn(B, H, S, D) * 0.5).astype(np.float32)
K = (RNG.randn(B, H, S, D) * 0.5).astype(np.float32)
V = (RNG.randn(B, H, S, D) * 0.5).astype(np.float32)
BIAS = np.zeros((B, 1, 1, S), np.float32)
BIAS[0, 0, 0, -4:] = -1e4
Z = np.zeros(1, np.int32)


def _f64_attention(q, k, v, bias):
    s = np.einsum("bhqd,bhkd->bhqk", q.astype(np.float64),
                  k.astype(np.float64)) * SCALE
    s = s + bias.astype(np.float64)
    e = np.exp(s - s.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v.astype(np.float64))


def test_interpret_mode_active():
    assert _supports_pallas(), "interpret mode should force the kernel path"


def test_forward_matches_fp64():
    out = fused_attention(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V),
                          jnp.asarray(BIAS))
    ref = _f64_attention(Q, K, V, BIAS)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_forward_mask_zeroes_attention():
    """Masked key columns must receive zero attention weight: make the
    masked V rows huge; the output must not move."""
    v2 = V.copy()
    v2[0, :, -4:, :] = 1e6
    out1 = fused_attention(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V),
                           jnp.asarray(BIAS))
    out2 = fused_attention(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(v2),
                           jnp.asarray(BIAS))
    np.testing.assert_allclose(np.asarray(out1)[0], np.asarray(out2)[0],
                               rtol=1e-5)


def test_gradients_match_fp64():
    """The hand-written backward kernel against fp64 finite truth (the
    jnp autodiff path itself carries ~1e-2 fp32 noise here, so fp64 is
    the only fair oracle)."""
    def f64_loss_grads():
        q = Q.astype(np.float64)
        k = K.astype(np.float64)
        v = V.astype(np.float64)
        s = np.einsum("bhqd,bhkd->bhqk", q, k) * SCALE + BIAS
        e = np.exp(s - s.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        o = np.einsum("bhqk,bhkd->bhqd", p, v)
        do = 2 * o
        dv = np.einsum("bhqk,bhqd->bhkd", p, do)
        dp = np.einsum("bhqd,bhkd->bhqk", do, v)
        ds = p * (dp - (dp * p).sum(-1, keepdims=True))
        dq = np.einsum("bhqk,bhkd->bhqd", ds, k) * SCALE
        dk = np.einsum("bhqk,bhqd->bhkd", ds, q) * SCALE
        return dq, dk, dv

    def loss(q, k, v):
        return jnp.sum(fused_attention(q, k, v, jnp.asarray(BIAS)) ** 2)

    got = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V))
    want = f64_loss_grads()
    for g, w, name in zip(got, want, "qkv"):
        scale = max(np.abs(w).max(), 1e-9)
        err = np.abs(np.asarray(g) - w).max() / scale
        assert err < 5e-3, (name, err)


def test_dropout_statistics_and_determinism():
    key = jax.random.PRNGKey(11)
    out1 = fused_attention(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V),
                           jnp.asarray(BIAS), dropout_prob=0.5, rng_key=key)
    out2 = fused_attention(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V),
                           jnp.asarray(BIAS), dropout_prob=0.5, rng_key=key)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))  # same key
    out3 = fused_attention(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V),
                           jnp.asarray(BIAS), dropout_prob=0.5,
                           rng_key=jax.random.PRNGKey(12))
    assert np.abs(np.asarray(out1) - np.asarray(out3)).max() > 1e-4
    # dropout keeps the output mean roughly unbiased (upscale_in_train)
    base = fused_attention(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V),
                           jnp.asarray(BIAS))
    outs = [np.asarray(fused_attention(
        jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V), jnp.asarray(BIAS),
        dropout_prob=0.5, rng_key=jax.random.PRNGKey(s)))
        for s in range(24)]
    mean = np.mean(outs, axis=0)
    denom = np.abs(np.asarray(base)).mean() + 1e-6
    assert np.abs(mean - np.asarray(base)).mean() / denom < 0.35


def test_dropout_gradient_uses_same_mask():
    """grad through the dropped forward: zeroed probability cells must
    contribute zero gradient; check grads are finite and nonzero."""
    key = jax.random.PRNGKey(5)

    def loss(q):
        return jnp.sum(fused_attention(q, jnp.asarray(K), jnp.asarray(V),
                                       jnp.asarray(BIAS), dropout_prob=0.3,
                                       rng_key=key) ** 2)

    g = np.asarray(jax.grad(loss)(jnp.asarray(Q)))
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_fluid_layer_path():
    """layers.fused_attention drives the op through a Program, grads flow
    into q/k/v producers."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [S, D * H], append_batch_size=False)
        qkv = layers.fc(x, 3 * H * D, name="qkv")
        q = layers.reshape(layers.slice(qkv, [1], [0], [H * D]),
                           [1, S, H, D])
        kk = layers.reshape(layers.slice(qkv, [1], [H * D], [2 * H * D]),
                            [1, S, H, D])
        vv = layers.reshape(layers.slice(qkv, [1], [2 * H * D],
                                         [3 * H * D]), [1, S, H, D])
        q = layers.transpose(q, [0, 2, 1, 3])
        kk = layers.transpose(kk, [0, 2, 1, 3])
        vv = layers.transpose(vv, [0, 2, 1, 3])
        out = layers.fused_attention(q, kk, vv)
        loss = layers.reduce_mean(layers.square(out))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    assert any(op.type == "fused_multihead_attention"
               for op in main.global_block().ops)
    exe = fluid.Executor()
    feed = {"x": RNG.rand(S, D * H).astype(np.float32)}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        vals = [float(np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[loss])[0]))
                for _ in range(6)]
    assert vals[-1] < vals[0]  # minimizing the mean square moves weights


def test_bias_gradient_reduced_in_kernel():
    """dbias comes back already reduced to the broadcast [B,1,1,S] shape
    and matches the jnp-autodiff reference."""
    def loss_fused(b):
        return jnp.sum(fused_attention(jnp.asarray(Q), jnp.asarray(K),
                                       jnp.asarray(V), b) ** 2)

    def loss_ref(b):
        return jnp.sum(_ref_attention(jnp.asarray(Q), jnp.asarray(K),
                                      jnp.asarray(V), b, SCALE, 0.0,
                                      Z) ** 2)

    g1 = np.asarray(jax.grad(loss_fused)(jnp.asarray(BIAS)))
    g2 = np.asarray(jax.grad(loss_ref)(jnp.asarray(BIAS)))
    assert g1.shape == BIAS.shape
    denom = max(np.abs(g2).max(), 1e-9)
    assert np.abs(g1 - g2).max() / denom < 5e-3


def test_blockwise_attention_matches_reference():
    """Long-seq fallback (online softmax over K blocks) must match the
    one-pass reference numerically, fwd and grad."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    rng = np.random.RandomState(0)
    B, H, S, d = 2, 3, 256, 8
    q = jnp.asarray(rng.randn(B, H, S, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, H, S, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, H, S, d).astype(np.float32) * 0.3)
    bias = jnp.asarray(
        np.where(rng.rand(B, 1, 1, S) < 0.2, -1e4, 0.0).astype(np.float32))
    seed = jnp.zeros((1,), jnp.int32)
    scale = d ** -0.5

    out_blk = A._blockwise_attention(q, k, v, bias, scale, 0.0, seed)
    out_ref = A._ref_attention(q, k, v, bias, scale, 0.0, seed)
    np.testing.assert_allclose(np.asarray(out_blk), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-6)

    def loss_blk(q_, k_, v_):
        return A._blockwise_attention(q_, k_, v_, bias, scale, 0.0,
                                      seed).sum()

    def loss_ref(q_, k_, v_):
        return A._ref_attention(q_, k_, v_, bias, scale, 0.0, seed).sum()

    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_blk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-6)


def test_fallback_dispatches_blockwise_past_vmem_bound(monkeypatch):
    from paddle_tpu.kernels import attention as A

    calls = []
    real = A._blockwise_attention
    monkeypatch.setattr(A, "_blockwise_attention",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
    rng = np.random.RandomState(1)
    import jax.numpy as jnp

    q = jnp.asarray(rng.randn(1, 2, 128, 8).astype(np.float32))
    bias = jnp.zeros((1, 1, 1, 128), jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)
    A._fallback_attention(q, q, q, bias, 0.35, 0.0, seed)
    assert calls, "blockwise path not taken past the bound"


def test_blockwise_dropout_normalizes_like_one_pass():
    """Denominator uses undropped weights: E[out] ~ one-pass output."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    rng = np.random.RandomState(2)
    B, H, S, d = 1, 2, 128, 4
    q = jnp.asarray(rng.randn(B, H, S, d).astype(np.float32) * 0.2)
    bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    outs = []
    for s in range(8):
        seed = jnp.asarray([s], jnp.int32)
        outs.append(np.asarray(A._blockwise_attention(
            q, q, q, bias, 0.5, 0.3, seed)))
    mean = np.mean(outs, axis=0)
    ref = np.asarray(A._ref_attention(q, q, q, bias, 0.5, 0.0,
                                      jnp.zeros((1,), jnp.int32)))
    np.testing.assert_allclose(mean, ref, rtol=0.35, atol=0.05)


def test_blockwise_attention_prime_seq_pads():
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    rng = np.random.RandomState(3)
    B, H, S, d = 1, 2, 131, 4  # prime S: must pad, not degrade to block=1
    q = jnp.asarray(rng.randn(B, H, S, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, H, S, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, H, S, d).astype(np.float32) * 0.3)
    bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)
    out = A._blockwise_attention(q, k, v, bias, 0.5, 0.0, seed)
    ref = A._ref_attention(q, k, v, bias, 0.5, 0.0, seed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


class TestLongKernel:
    """Q-tiled long-seq kernels: fwd + custom-vjp bwd vs the reference,
    exercised through the interpreter with _MAX_FUSED_SEQ patched below S
    so the long path engages (S % _QB_LONG == 0)."""

    def _setup(self, monkeypatch, bias_shape):
        from paddle_tpu.kernels import attention as A

        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 128)
        rng = np.random.RandomState(7)
        b, h, s, d = 1, 2, 256, 8
        q = jnp.asarray((rng.randn(b, h, s, d) * 0.4).astype(np.float32))
        k = jnp.asarray((rng.randn(b, h, s, d) * 0.4).astype(np.float32))
        v = jnp.asarray((rng.randn(b, h, s, d) * 0.4).astype(np.float32))
        bias = np.zeros(bias_shape, np.float32)
        bias[..., -5:] = -1e4
        return A, q, k, v, jnp.asarray(bias), 1.0 / np.sqrt(d)

    def test_long_path_taken(self, monkeypatch):
        A, q, k, v, bias, scale = self._setup(monkeypatch, (1, 1, 1, 256))
        assert A._use_long_kernel(q, 0.0, bias)
        assert not A._use_kernel(q, 0.0)

    def test_head_broadcast_per_row_bias_takes_blockwise(self, monkeypatch):
        # [B,1,S,S] bias with H>1: dbias would need non-consecutive
        # revisit accumulation — must decline the long kernel
        A, q, k, v, bias, scale = self._setup(monkeypatch, (1, 1, 256, 256))
        assert not A._use_long_kernel(q, 0.0, bias)
        seed = jnp.zeros((1,), jnp.int32)
        out = A._fused(q, k, v, bias, scale, 0.0, seed)
        ref = A._ref_attention(q, k, v, bias, scale, 0.0, seed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("bias_shape", [(1, 1, 1, 256), (1, 2, 256, 256)])
    def test_forward_matches_reference(self, monkeypatch, bias_shape):
        A, q, k, v, bias, scale = self._setup(monkeypatch, bias_shape)
        seed = jnp.zeros((1,), jnp.int32)
        out = A._pallas_attention_long(q, k, v, bias, scale, 0.0, seed)
        ref = A._ref_attention(q, k, v, bias, scale, 0.0, seed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("bias_shape", [(1, 1, 1, 256), (1, 2, 256, 256)])
    def test_grads_match_reference(self, monkeypatch, bias_shape):
        A, q, k, v, bias, scale = self._setup(monkeypatch, bias_shape)
        seed = jnp.zeros((1,), jnp.int32)

        def loss_fused(q_, k_, v_, b_):
            return (A._fused(q_, k_, v_, b_, scale, 0.0, seed) ** 2).sum()

        def loss_ref(q_, k_, v_, b_):
            return (A._ref_attention(q_, k_, v_, b_, scale, 0.0,
                                     seed) ** 2).sum()

        g_f = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(q, k, v, bias)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-5)


@pytest.mark.skipif(not _supports_pallas(), reason="no pallas")
class TestFlashKernels:
    """Flash tier (_flash_* kernels): online-softmax forward + split
    dq / dk·dv backward pair, exercised through the interpreter with the
    lower tiers patched off and a small tile edge so multi-tile online
    accumulation runs (S=256 at Tb=64 -> 4x4 tiles)."""

    def _setup(self, monkeypatch, bias_shape):
        from paddle_tpu.kernels import attention as A

        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
        monkeypatch.setattr(A, "_MAX_LONG_SEQ", 0)
        monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (64,))
        rng = np.random.RandomState(11)
        b, h, s, d = 1, 2, 256, 8
        q = jnp.asarray((rng.randn(b, h, s, d) * 0.4).astype(np.float32))
        k = jnp.asarray((rng.randn(b, h, s, d) * 0.4).astype(np.float32))
        v = jnp.asarray((rng.randn(b, h, s, d) * 0.4).astype(np.float32))
        bias = np.zeros(bias_shape, np.float32)
        bias[..., -7:] = -1e4
        return A, q, k, v, jnp.asarray(bias), 1.0 / np.sqrt(d)

    def test_flash_path_taken(self, monkeypatch):
        A, q, k, v, bias, scale = self._setup(monkeypatch, (1, 1, 1, 256))
        assert A._use_flash_kernel(q, 0.0, bias)
        assert not A._use_kernel(q, 0.0)
        assert not A._use_long_kernel(q, 0.0, bias)

    def test_per_row_bias_declines(self, monkeypatch):
        # per-row bias would need [B,H,S,S] dbias partials — blockwise
        # path takes it and still matches the reference
        A, q, k, v, bias, scale = self._setup(monkeypatch, (1, 1, 256, 256))
        assert not A._use_flash_kernel(q, 0.0, bias)
        seed = jnp.zeros((1,), jnp.int32)
        out = A._fused(q, k, v, bias, scale, 0.0, seed)
        ref = A._ref_attention(q, k, v, bias, scale, 0.0, seed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_indivisible_seq_declines(self, monkeypatch):
        A, _, _, _, _, _ = self._setup(monkeypatch, (1, 1, 1, 256))
        assert A._flash_block(250) is None

    @pytest.mark.parametrize("bias_shape", [(1, 1, 1, 256), (1, 2, 1, 256)])
    def test_forward_matches_reference(self, monkeypatch, bias_shape):
        A, q, k, v, bias, scale = self._setup(monkeypatch, bias_shape)
        seed = jnp.zeros((1,), jnp.int32)
        out, lse = A._pallas_attention_flash(q, k, v, bias, scale, 0.0,
                                             seed)
        ref = A._ref_attention(q, k, v, bias, scale, 0.0, seed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        # the saved logsumexp must be the true row logsumexp
        s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q),
                      np.asarray(k)) * scale
        s = s + np.broadcast_to(np.asarray(bias),
                                (1, bias.shape[1], 1, 256))
        ref_lse = np.log(np.exp(s - s.max(-1, keepdims=True))
                         .sum(-1)) + s.max(-1)
        got_lse = np.asarray(lse)[..., 0]
        if bias.shape[1] == 1:
            ref_lse = np.broadcast_to(ref_lse, got_lse.shape)
        np.testing.assert_allclose(got_lse, ref_lse, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bias_shape", [(1, 1, 1, 256), (1, 2, 1, 256)])
    def test_grads_match_reference(self, monkeypatch, bias_shape):
        A, q, k, v, bias, scale = self._setup(monkeypatch, bias_shape)
        seed = jnp.zeros((1,), jnp.int32)

        def loss_fused(q_, k_, v_, b_):
            return (A._fused(q_, k_, v_, b_, scale, 0.0, seed) ** 2).sum()

        def loss_ref(q_, k_, v_, b_):
            return (A._ref_attention(q_, k_, v_, b_, scale, 0.0,
                                     seed) ** 2).sum()

        g_f = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(q, k, v, bias)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-5)

    def test_grads_match_blockwise_production_tile_picker(self, monkeypatch):
        """Same check against the blockwise oracle with the production
        tile picker (Tb=128 via candidates) and uneven value scales."""
        from paddle_tpu.kernels import attention as A

        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
        monkeypatch.setattr(A, "_MAX_LONG_SEQ", 0)
        rng = np.random.RandomState(5)
        b, h, s, d = 1, 1, 384, 8
        q = jnp.asarray((rng.randn(b, h, s, d)).astype(np.float32))
        k = jnp.asarray((rng.randn(b, h, s, d)).astype(np.float32))
        v = jnp.asarray((rng.randn(b, h, s, d) * 2.0).astype(np.float32))
        bias = np.zeros((b, 1, 1, s), np.float32)
        bias[..., :11] = -1e4
        bias = jnp.asarray(bias)
        assert A._flash_block(s) == 128
        seed = jnp.zeros((1,), jnp.int32)
        scale = 1.0 / np.sqrt(d)

        def loss_fused(q_, k_, v_, b_):
            return (A._fused(q_, k_, v_, b_, scale, 0.0, seed) ** 2).sum()

        def loss_blk(q_, k_, v_, b_):
            return (A._blockwise_attention(q_, k_, v_, b_, scale, 0.0,
                                           seed) ** 2).sum()

        g_f = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(q, k, v, bias)
        g_b = jax.grad(loss_blk, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b_ in zip(g_f, g_b):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=3e-4, atol=3e-5)


def _dispatch_case(A, monkeypatch, tier):
    """The call that, at its shape, takes ``tier`` (None: the jnp
    fallback) with the thresholds patched down to interpreter sizes."""
    rng = np.random.RandomState(17)

    def mk(*shape):
        return jnp.asarray((rng.randn(*shape) * 0.4).astype(np.float32))

    if tier in ("decode", "paged"):
        b, h, d, ptok, npages = 2, 2, 8, 8, 4
        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", npages * ptok)
        q, lens = mk(b, h, 1, d), np.array([5, 29], np.int32)
        if tier == "decode":
            return lambda: A.attention_with_cache(
                q, mk(b, h, npages * ptok, d), mk(b, h, npages * ptok, d),
                lens)
        table = np.arange(1, b * npages + 1, dtype=np.int32).reshape(
            b, npages)
        pool = (mk(b * npages + 1, h, ptok, d),
                mk(b * npages + 1, h, ptok, d))
        return lambda: A.paged_attention_cache(q, *pool, table, lens)
    # training attention: S alone walks block -> long -> flash -> fallback
    s = {"block": 64, "long": 256, "flash": 128, "select": 128,
         None: 96}[tier]
    if tier == "long":
        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 128)
    elif tier in ("flash", None):
        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
        monkeypatch.setattr(A, "_MAX_LONG_SEQ", 0)
        monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (64,))
    q, k, v = mk(1, 2, s, 8), mk(1, 2, s, 8), mk(1, 2, s, 8)
    if tier == "select":
        keep = np.tril(rng.randint(0, 2, (1, s, s))) | np.eye(s, dtype=int)
        sel = jnp.asarray(keep.astype(np.int8))
        return lambda: A.fused_attention(q, k, v, causal=True, select=sel)
    return lambda: A.fused_attention(q, k, v)


@pytest.mark.skipif(not _supports_pallas(), reason="no pallas")
@pytest.mark.parametrize("tier", ["block", "long", "flash", "select",
                                  "decode", "paged", None])
def test_shape_alone_picks_tier(monkeypatch, tier):
    """Each tier is reached by the call's shapes and the module's
    thresholds alone: exactly its ``attn_kernel_dispatch_total{tier}``
    moves (none for the jnp fallback, at a length no tile divides)."""
    from paddle_tpu.fluid import monitor
    from paddle_tpu.kernels import attention as A

    def counts():
        return {t: monitor.counter("attn_kernel_dispatch_total",
                                   labels={"tier": t}).value
                for t in A.KERNEL_TIERS}

    call = _dispatch_case(A, monkeypatch, tier)
    before = counts()
    jax.block_until_ready(call())
    after = counts()
    moved = {t for t in A.KERNEL_TIERS if after[t] != before[t]}
    assert moved == ({tier} if tier else set()), moved
