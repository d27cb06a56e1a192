"""``causal`` and grouped-query attention in ``kernels/attention.py``: the
mask is made inside the kernels from row and column indices, in every
training tier (block, long, flash - under the Pallas interpreter - and the
blockwise fallback), against ``_ref_attention`` with an explicit causal
bias; K/V with fewer heads than Q through the ``fused_multihead_attention``
op; a call without ``causal`` traces to the jaxpr it always did; and the
flash and select tiers walk the causal triangle alone - the tiles wholly
above the diagonal neither fetched nor computed, nor given a grid step,
the diagonal tile in strips that leave its upper blocks out - and count
that work in ``attn_flash_tiles_total`` and ``attn_causal_work_total``.

Every test here cuts the diagonal tile into strips of a quarter of it,
in the forward kernel too (``_DIAG_MIN_EDGE`` lowered to the toy tiles,
``_DIAG_FWD_MIN_D`` to their head widths)."""

import os

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import attention as A


@pytest.fixture(autouse=True)
def _strips(monkeypatch):
    monkeypatch.setattr(A, "_DIAG_MIN_EDGE", 8)
    monkeypatch.setattr(A, "_DIAG_FWD_MIN_D", 0)


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


# -- the causal triangle: tiles, strips, dropout, the counter ----------------
def test_diagonal_strips_by_head_width(monkeypatch):
    """At the chip's tile of 1024: strips of 256 in dq and dk/dv at every
    head width, and in the forward from d = 192 on; below that the forward
    keeps the diagonal tile whole. No strip under the 128-lane floor."""
    monkeypatch.undo()
    for d in (64, 128, 192, 256):
        assert A._diag_edge(1024, d) == 256
        assert A._diag_edge(1024, d, forward=True) == (256 if d >= 192
                                                       else 1024)
    assert A._diag_edge(512, 256) == 128
    assert A._diag_edge(256, 256) == 128
    assert A._diag_edge(128, 256, forward=True) == 128
    assert list(A._diag_strips(1024, 256, rows=True))[2] == (
        slice(512, 768), [(slice(0, 512), False), (slice(512, 768), True)])
    assert list(A._diag_strips(1024, 256, rows=False))[3] == (
        slice(768, 1024), [(slice(768, 1024), True)])


def _flash_case(monkeypatch, nt, dv=None, S=256):
    """The flash tier at S = 256 in nt x nt tiles, the padded-tail bias,
    K/V repeated from 2 heads to 4; ``dv`` a narrower v (192 / 128 is
    latent attention's pair)."""
    _, taken = _tier(monkeypatch, "flash")
    monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (S // nt,))
    d = 8 if dv is None else 192
    q, k, v, pad = _qkv(S, d=d)
    if dv is not None:
        v = v[..., :dv]
    assert taken(q, pad) and S // A._flash_block(S) == nt
    return q, k, v, pad


def _select_case(nt, Hkv, S=256, H=4, d=8, B=2):
    """The select tier at S = 256 in nt x nt tiles under a random
    selection inside the causal triangle, the diagonal kept and whole
    blocks of a row empty (keys 0-63 barred to rows 128-191)."""
    A._SELECT_BLOCK_CANDIDATES = (S // nt,)
    rng = np.random.RandomState(nt + Hkv)
    q = jnp.asarray(0.5 * rng.randn(B, H, S, d), jnp.float32)
    k, v = (jnp.asarray(0.5 * rng.randn(B, Hkv, S, d), jnp.float32)
            for _ in "kv")
    keep = rng.rand(B, S, S) < 0.3
    keep[:, 128:192, :64] = False
    keep = (keep | np.eye(S, dtype=bool)) & np.tril(np.ones((S, S), bool))
    select = jnp.asarray(keep.astype(np.int8))
    assert A._use_select_kernel(q, k, True)
    return q, k, v, select


def _fwd_bwd(fn, args, do):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(do)


def _flash_fn(pad):
    def fn(q_, k_, v_, pad_=pad):
        rep = q_.shape[1] // k_.shape[1]
        return A.fused_attention(q_, jnp.repeat(k_, rep, 1),
                                 jnp.repeat(v_, rep, 1), pad_,
                                 scale=0.3, causal=True)
    return fn


def _select_fn(select):
    return lambda q_, k_, v_: A.fused_attention(
        q_, k_, v_, scale=0.3, causal=True, select=select)


@pytest.mark.parametrize("nt", [1, 2, 4])
@pytest.mark.parametrize("case", ["flash", "flash-192/128", "select-mha",
                                  "select-gqa"])
def test_causal_tiers_match_the_reference(monkeypatch, case, nt):
    """o and every gradient (the bias's too) of the flash tier - bias, K/V
    repeated from 2 heads, one width or latent attention's two - and of
    the select tier under a random selection, at 1, 2 and 4 tiles a row,
    against ``_ref_attention`` with the mask (and the selection) as a
    bias."""
    if case.startswith("flash"):
        q, k, v, pad = _flash_case(monkeypatch, nt,
                                   dv=128 if "/" in case else None)
        args, fn = (q, k, v, pad), _flash_fn(pad)
        mask = _causal_bias(q.shape[2])
    else:
        monkeypatch.setattr(A, "_SELECT_BLOCK_CANDIDATES", ())
        q, k, v, select = _select_case(nt, Hkv=2 if "gqa" in case else 4)
        args, fn = (q, k, v), _select_fn(select)
        mask = jnp.where(select != 0, 0.0, -1e30)[:, None]
    rep = q.shape[1] // k.shape[1]

    def ref(q_, k_, v_, pad_=0.0):
        return A._ref_attention(q_, jnp.repeat(k_, rep, 1),
                                jnp.repeat(v_, rep, 1), pad_ + mask, 0.3,
                                0.0, None)

    do = jnp.asarray(np.random.RandomState(5).randn(
        *q.shape[:3], v.shape[-1]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _fwd_bwd(fn, args, do)
        want = _fwd_bwd(ref, args, do)
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv", "dbias")):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("tier", ["flash", "select"])
def test_strips_match_the_whole_tile_form(monkeypatch, tier):
    """The diagonal tile in 4 strips against it worked whole and masked
    (one strip: what each kernel computed before the strips), 4 x 4
    tiles, the three kernels called alone: equal but for the order of the
    sums inside the diagonal tile. Given the same o and L, and dO = 0 on
    the first q-tile, dK and dV of the first k-tile are made by the tiles
    below the diagonal alone - the tiles the strips leave as they were -
    and match to the bit."""
    if tier == "flash":
        q, k, v, pad = _flash_case(monkeypatch, 4)
        k, v = (jnp.repeat(t, 2, 1) for t in (k, v))
        seed = jnp.zeros((1,), jnp.int32)

        def fwd():
            return A._pallas_attention_flash(q, k, v, pad, 0.3, 0.0, seed,
                                             causal=True)

        def bwd(do, o, lse):
            return A._pallas_attention_flash_bwd(q, k, v, pad, seed, do, o,
                                                 lse, 0.3, 0.0, causal=True)
    else:
        monkeypatch.setattr(A, "_SELECT_BLOCK_CANDIDATES", ())
        q, k, v, select = _select_case(4, Hkv=2)

        def fwd():
            return A._pallas_attention_select(q, k, v, select, 0.3)

        def bwd(do, o, lse):
            return A._pallas_attention_select_bwd(q, k, v, select, do, o,
                                                  lse, 0.3)
    tb = q.shape[2] // 4
    do = jnp.asarray(np.random.RandomState(6).randn(*q.shape),
                     jnp.float32).at[:, :, :tb].set(0.0)
    assert A._diag_edge(tb, q.shape[-1]) == tb // 4
    o, lse = strips = fwd()
    strips += bwd(do, o, lse)
    monkeypatch.setattr(A, "_DIAG_MIN_EDGE", tb)
    assert A._diag_edge(tb, q.shape[-1]) == tb
    whole = fwd() + bwd(do, o, lse)
    names = ("o", "lse", "dq", "dk", "dv", "dbias")
    for a, b, name in zip(strips, whole, names):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
    for a, b, name in zip(strips[3:5], whole[3:5], ("dk", "dv")):
        np.testing.assert_array_equal(a[:, :, :tb], b[:, :, :tb],
                                      err_msg=name)
        assert np.abs(np.asarray(a[:, :, :tb])).max() > 0


_MIX = (0x9E3779B1, 0x85EBCA6B, 0x7FEB352D, 0x846CA68B)


def _bits(seed, shape, xp):
    """A stand-in for the TPU's PRNG bits: a hash of the seed and the
    position in the ``shape`` draw, in ``xp`` (jnp in a kernel, numpy in
    the oracle) on uint32."""
    u32 = xp.uint32
    idx = (xp.arange(shape[0], dtype=u32)[:, None] * u32(shape[1])
           + xp.arange(shape[1], dtype=u32)[None, :])
    x = xp.asarray(seed).astype(u32) * u32(_MIX[0]) ^ idx * u32(_MIX[1])
    for m in _MIX[2:]:
        x = (x ^ (x >> u32(16))) * u32(m)
    return x ^ (x >> u32(15))


@pytest.mark.parametrize("nt", [1, 4])
def test_causal_dropout_drops_the_same_pairs_in_all_three_kernels(
        monkeypatch, nt):
    """Dropout under ``causal``, the PRNG replaced by a hash of (seed,
    position in the tile's draw): the forward, dq and dk/dv kernels -
    below the diagonal whole, on it strip by strip - against a reference
    that drops the pairs the tiles' seeds name (``_flash_seed``)."""
    with np.errstate(over="ignore"):
        _dropout_case(monkeypatch, nt)


def _dropout_case(monkeypatch, nt):
    q, k, v, pad = _flash_case(monkeypatch, nt)
    k, v = (jnp.repeat(t, 2, 1) for t in (k, v))
    B, H, S, _ = q.shape
    tb, p_drop, seed0 = S // nt, 0.3, 1234
    drawn = []
    monkeypatch.setattr(A.pltpu, "prng_seed", drawn.append)
    monkeypatch.setattr(A.pltpu, "prng_random_bits",
                        lambda shape: _bits(drawn[-1], shape, jnp))
    keep = np.zeros((B, H, S, S), bool)
    for b in range(B):
        for h in range(H):
            for i in range(nt):
                for j in range(i + 1):
                    u = A._uniform_from_bits(_bits(
                        A._flash_seed(seed0, b, h, i, j, H, nt, nt),
                        (tb, tb), np))
                    keep[b, h, i * tb:(i + 1) * tb,
                         j * tb:(j + 1) * tb] = np.asarray(u) >= p_drop
    seed = jnp.asarray([seed0], jnp.int32)
    do = jnp.asarray(np.random.RandomState(8).randn(*q.shape), jnp.float32)

    def kernels(q_, k_, v_, pad_):
        o, lse = A._pallas_attention_flash(q_, k_, v_, pad_, 0.3, p_drop,
                                           seed, causal=True)
        return (o,) + A._pallas_attention_flash_bwd(
            q_, k_, v_, pad_, seed, do, o, lse, 0.3, p_drop, causal=True)

    def ref(q_, k_, v_, pad_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * 0.3 + pad_
        p = jax.nn.softmax(s + _causal_bias(S), axis=-1)
        p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v_)

    with jax.default_matmul_precision("highest"):
        got = kernels(q, k, v, pad)
        want = _fwd_bwd(ref, (q, k, v, pad), do)
    assert 0.25 < 1.0 - keep[np.tril(np.ones((S, S), bool))[None, None]
                             & np.ones((B, H, 1, 1), bool)].mean() < 0.35
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv", "dbias")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("tier", ["flash", "select"])
def test_causal_work_counter(monkeypatch, tier):
    """``attn_causal_work_total``: once a traced kernel site (a forward
    and backward are three), 4 x 4 tiles of 64 a (batch row, head or K/V
    group of 2): 10 grid steps, none empty; the 6 tiles below the diagonal
    and the diagonal ones in 4 strips of 16 computed, 1,920 scores masked
    - against 4 x 64 x 63 / 2 = 8,064 of 40,960 with the diagonal tiles
    whole. The flash tier's ``attn_flash_tiles_total`` reads its (10, 6)
    as before."""
    from paddle_tpu.fluid import monitor

    def read(name, labels, kinds):
        return np.array([monitor.counter(name, labels=dict(labels, kind=k))
                         .value for k in kinds])

    if tier == "flash":
        q, k, v, pad = _flash_case(monkeypatch, 4)
        fn, G = _flash_fn(pad), 1
    else:
        monkeypatch.setattr(A, "_SELECT_BLOCK_CANDIDATES", ())
        q, k, v, select = _select_case(4, Hkv=2)
        fn, G = _select_fn(select), 2

    def traced(grad):
        loss = lambda *a: jnp.sum(fn(*a))      # noqa: E731
        counters = (("attn_causal_work_total", {"tier": tier},
                     ("steps", "empty_steps", "scores", "masked_scores")),
                    ("attn_flash_tiles_total", {}, ("computed", "skipped")))
        before = [read(*c) for c in counters]
        jax.make_jaxpr(jax.grad(loss, (0, 1, 2)) if grad else loss)(q, k, v)
        return [read(*c) - b for c, b in zip(counters, before)]

    site = np.array([10, 0, G * (6 * 64 * 64 + 4 * 10 * 16 * 16),
                     G * 4 * 4 * 16 * 15 // 2])
    tiles = np.array((10, 6) if tier == "flash" else (0, 0))
    for grad, sites in ((False, 1), (True, 3)):
        work, flash_tiles = traced(grad)
        np.testing.assert_array_equal(work, sites * site)
        np.testing.assert_array_equal(flash_tiles, sites * tiles)
    monkeypatch.setattr(A, "_DIAG_MIN_EDGE", 64)
    whole, _ = traced(False)
    np.testing.assert_array_equal(whole, [10, 0, G * 40960, G * 8064])
    assert work[3] / work[2] < 0.06 < 0.19 < whole[3] / whole[2]
