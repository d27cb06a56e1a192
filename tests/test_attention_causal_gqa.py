"""``causal`` and grouped-query attention in ``kernels/attention.py``: the
mask is made inside the kernels from row and column indices, in every
training tier (block, long, flash - under the Pallas interpreter - and the
blockwise fallback), against ``_ref_attention`` with an explicit causal
bias; K/V with fewer heads than Q through the ``fused_multihead_attention``
op; a call without ``causal`` traces to the jaxpr it always did; and the
flash tier skips the k-tiles wholly above the diagonal (neither fetched
nor computed) and counts them in ``attn_flash_tiles_total``."""

import os

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import attention as A


def _tier(monkeypatch, tier):
    """Shapes and patches under which ``_fused`` takes ``tier`` on the
    CPU; returns ``(S, check)`` where ``check(q, bias)`` asserts it."""
    if tier == "block":
        return 128, lambda q, b: A._use_kernel(q, 0.0)
    if tier == "long":
        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 128)
        return 256, lambda q, b: A._use_long_kernel(q, 0.0, b)
    if tier == "flash":     # 4 x 4 tiles: rows above, on and below the mask
        monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
        monkeypatch.setattr(A, "_MAX_LONG_SEQ", 0)
        monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (64,))
        return 256, lambda q, b: A._use_flash_kernel(q, 0.0, b)
    assert tier == "blockwise"
    monkeypatch.setattr(A, "_supports_pallas", lambda: False)
    monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
    return 1088, lambda q, b: not (     # 1088 = 2 x 512 + 64: padded keys
        A._use_kernel(q, 0.0) or A._use_long_kernel(q, 0.0, b)
        or A._use_flash_kernel(q, 0.0, b))


def _qkv(S, H=4, Hkv=2, d=8, B=2):
    rng = np.random.RandomState(S)
    q = jnp.asarray(0.5 * rng.randn(B, H, S, d), jnp.float32)
    k = jnp.asarray(0.5 * rng.randn(B, Hkv, S, d), jnp.float32)
    v = jnp.asarray(0.5 * rng.randn(B, Hkv, S, d), jnp.float32)
    pad = np.zeros((B, 1, 1, S), np.float32)
    pad[1, ..., -9:] = -1e4          # a padded tail on one row, besides
    return q, k, v, jnp.asarray(pad)


def _causal_bias(S):
    return jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None],
                     0.0, -1e30)[None, None]


def _reference(q, k, v, pad, scale):
    """``_ref_attention`` with the mask as an explicit [S, S] bias and
    the KV heads repeated."""
    rep = q.shape[1] // k.shape[1]
    return A._ref_attention(
        q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
        pad + _causal_bias(q.shape[2]), scale, 0.0, None)


TIERS = ["block", "long", "flash", "blockwise"]


@pytest.mark.parametrize("tier", TIERS)
def test_causal_gqa_forward_matches_reference(monkeypatch, tier):
    S, taken = _tier(monkeypatch, tier)
    q, k, v, pad = _qkv(S)
    rep = q.shape[1] // k.shape[1]
    assert taken(q, pad)
    got = A.fused_attention(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                            pad, causal=True)
    want = _reference(q, k, v, pad, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # causal: the first position sees itself alone
    np.testing.assert_allclose(got[:, :, 0], jnp.repeat(v, rep, 1)[:, :, 0],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tier", TIERS)
def test_causal_gqa_gradients_match_reference(monkeypatch, tier):
    S, _ = _tier(monkeypatch, tier)
    q, k, v, pad = _qkv(S)
    rep = q.shape[1] // k.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])

    def fused(q_, k_, v_):      # the repeat's transpose sums a group's dK
        return jnp.sum(jnp.sin(A.fused_attention(
            q_, jnp.repeat(k_, rep, 1), jnp.repeat(v_, rep, 1), pad,
            causal=True)))

    def ref(q_, k_, v_):
        return jnp.sum(jnp.sin(_reference(q_, k_, v_, pad, scale)))

    got = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-5, err_msg=name)


def test_flash_skips_the_tiles_above_the_diagonal(monkeypatch):
    """V's last k-tile is NaN. Q-tiles 0..2 lie wholly before it, so a
    kernel that skips their last k-tile never reads it and returns what
    the clean run returns (one that computes it multiplies NaN by p = 0
    and returns NaN there); the last q-tile sees it and is NaN."""
    S, taken = _tier(monkeypatch, "flash")
    q, k, v, pad = _qkv(S, Hkv=4)
    assert taken(q, pad)
    tb = A._flash_block(S)
    clean = A.fused_attention(q, k, v, pad, causal=True)
    got = A.fused_attention(q, k, v.at[:, :, -tb:].set(jnp.nan), pad,
                            causal=True)
    np.testing.assert_array_equal(got[:, :, :-tb], clean[:, :, :-tb])
    assert np.isfinite(clean).all()
    assert np.isnan(got[:, :, -tb:]).all()


@pytest.mark.parametrize("tiles", [4, 1])
def test_flash_causal_gradients_with_dbias_match_reference(monkeypatch,
                                                           tiles):
    """dq, dk, dv and the gradient of the padded-tail bias with the
    skipped tiles (4 x 4: their dbias partials are written as zeros) and
    with one tile (S = Tb: nothing to skip)."""
    S, taken = _tier(monkeypatch, "flash")
    monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (S // tiles,))
    q, k, v, pad = _qkv(S, Hkv=4)
    assert taken(q, pad) and S // A._flash_block(S) == tiles
    scale = 1.0 / np.sqrt(q.shape[-1])

    def fused(q_, k_, v_, pad_):
        return jnp.sum(jnp.sin(A.fused_attention(q_, k_, v_, pad_,
                                                 causal=True)))

    def ref(q_, k_, v_, pad_):
        return jnp.sum(jnp.sin(_reference(q_, k_, v_, pad_, scale)))

    np.testing.assert_allclose(
        A.fused_attention(q, k, v, pad, causal=True),
        _reference(q, k, v, pad, scale), rtol=2e-5, atol=2e-6)
    got = jax.grad(fused, argnums=(0, 1, 2, 3))(q, k, v, pad)
    want = jax.grad(ref, argnums=(0, 1, 2, 3))(q, k, v, pad)
    for a, b, name in zip(got, want, ("q", "k", "v", "bias")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-5, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_tiles_counter(monkeypatch, causal):
    """``attn_flash_tiles_total``: once a traced kernel site, the 4 x 4
    tiles a (batch, head) it computes and skips - the forward alone is
    one site, a forward and backward three (forward, dq, dk/dv)."""
    from paddle_tpu.fluid import monitor

    S, _ = _tier(monkeypatch, "flash")
    q, k, v, pad = _qkv(S, Hkv=4)

    def read():
        return tuple(monitor.counter("attn_flash_tiles_total",
                                     labels={"kind": kind}).value
                     for kind in ("computed", "skipped"))

    def loss(q_, k_, v_):
        return jnp.sum(A.fused_attention(q_, k_, v_, pad, causal=causal))

    site = (10, 6) if causal else (16, 0)
    c0 = read()
    jax.make_jaxpr(loss)(q, k, v)
    c1 = read()
    assert tuple(b - a for a, b in zip(c0, c1)) == site
    jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)
    assert tuple(b - a for a, b in zip(c1, read())) == tuple(
        3 * n for n in site)


def test_op_takes_causal_and_num_kv_heads():
    """Through ``fluid.layers.fused_attention`` and ``Executor.run``: K
    and V arrive with 2 heads for Q's 4."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    q, k, v, _ = _qkv(128)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qv = layers.data("q", shape=list(q.shape), append_batch_size=False)
        kv = layers.data("k", shape=list(k.shape), append_batch_size=False)
        vv = layers.data("v", shape=list(v.shape), append_batch_size=False)
        out = layers.fused_attention(qv, kv, vv, causal=True, num_kv_heads=2)
    with fluid.scope_guard(fluid.Scope()):
        (got,) = fluid.Executor().run(
            main, feed={"q": np.asarray(q), "k": np.asarray(k),
                        "v": np.asarray(v)}, fetch_list=[out])
    want = _reference(q, k, v, jnp.zeros((2, 1, 1, 128)),
                      1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("tier", ["block", "long", "flash"])
def test_call_without_causal_traces_to_the_same_jaxpr(monkeypatch, tier):
    """A caller that never heard of ``causal`` gets the program it always
    got: the same jaxpr, kernel bodies included, as with ``causal=False``
    spelled out, forward and backward, and no index mask anywhere in it;
    the causal one differs from it by the ``iota`` pair and the select."""
    S, _ = _tier(monkeypatch, tier)
    q, k, v, pad = _qkv(S, Hkv=4)

    def text(**kw):
        f = lambda q_, k_, v_: jnp.sum(A.fused_attention(   # noqa: E731
            q_, k_, v_, pad, **kw))
        return str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(q, k, v))

    plain = text()
    assert plain == text(causal=False)
    assert "iota" not in plain
    masked = text(causal=True)
    assert masked != plain and "iota" in masked
