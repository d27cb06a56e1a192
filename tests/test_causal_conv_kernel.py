"""The short convolution's Pallas kernels (kernels/causal_conv.py), run
through the pallas interpreter on the CPU so the real kernel bodies
execute: forward, dx and dw against a plain loop in float64 and against
the XLA form (the oracle that stays in the tree), the halo at every tile
border, which implementation a shape takes and where a step's kernels are
filed."""

import os

import numpy as np
import pytest

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import monitor, profiler
from paddle_tpu.fluid.ops import linear_attention
from paddle_tpu.kernels import causal_conv

C = 256     # two lane tiles: the kernels walk a block 128 channels at a time


def _loop(x, w, dy, silu):
    """Position by position in float64: the definition, and its
    gradients written out tap by tap."""
    x, w, dy = (np.asarray(t, np.float64) for t in (x, w, dy))
    B, S, _ = x.shape
    K = w.shape[1]
    z = np.zeros_like(x)
    for t in range(S):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                z[:, t] += w[:, j] * x[:, t - (K - 1) + j]
    s = 1.0 / (1.0 + np.exp(-z))
    y, dz = (z * s, dy * s * (1.0 + z * (1.0 - s))) if silu else (z, dy)
    dx, dw = np.zeros_like(x), np.zeros_like(w)
    for t in range(S):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                dx[:, t - (K - 1) + j] += w[:, j] * dz[:, t]
                dw[:, j] += (dz[:, t] * x[:, t - (K - 1) + j]).sum(0)
    return y, dx, dw


def _inputs(S, K, dtype, B=2):
    ks = jax.random.split(jax.random.PRNGKey(S + K), 3)
    x = jax.random.normal(ks[0], (B, S, C))
    w = jax.random.normal(ks[1], (C, K)) * 0.5
    dy = jax.random.normal(ks[2], (B, S, C))
    return tuple(t.astype(dtype) for t in (x, w, dy))


def _three(conv, x, w, dy):
    y, vjp = jax.vjp(conv, x, w)
    return (y,) + vjp(dy)


CASES = [(dtype, K, act) for dtype in ("float32", "bfloat16")
         for K in (2, 4) for act in ("", "swish")]


@pytest.mark.parametrize("dtype, K, act", CASES)
def test_forward_dx_and_dw_match_the_loop_and_the_xla_form(dtype, K, act):
    """S = 512: two tiles of 256 a row, B = 2, every value non-zero, so a
    wrong halo at the border or a batch mixed into another shows."""
    x, w, dy = _inputs(512, K, dtype)
    assert causal_conv.supported(x.shape, w.shape, x.dtype)
    silu = act == "swish"
    got = _three(lambda x, w: causal_conv.causal_conv_pallas(x, w, silu),
                 x, w, dy)
    xla = _three(linear_attention._causal_conv(silu), x, w, dy)
    want = _loop(x, w, dy, silu)
    # one rounding of an f32 result: half a unit in the last place of bf16
    tol = 1e-5 if dtype == "float32" else 2.0 ** -8
    for a, b, c, name in zip(got, xla, want, ("y", "dx", "dw")):
        assert a.dtype == b.dtype == jnp.dtype(dtype), name
        scale = float(np.abs(c).max())
        for other in (b, c):
            np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(other, np.float64),
                rtol=tol, atol=tol * scale, err_msg=name)


@pytest.mark.parametrize("act", ["", "swish"])
def test_the_xla_form_matches_the_loop_at_a_toy_shape(act):
    """C = 96, S = 100: what the models' tests run; ``causal_conv`` takes
    the XLA form there, activation folded in, ``silu'`` in its backward."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x, dy = (jax.random.normal(k, (2, 100, 96)) for k in ks[:2])
    w = jax.random.normal(ks[2], (96, 4)) * 0.5
    assert not causal_conv.supported(x.shape, w.shape, x.dtype)
    got = _three(lambda x, w: linear_attention.causal_conv(x, w, act),
                 x, w, dy)
    for a, b, name in zip(got, _loop(x, w, dy, act == "swish"),
                          ("y", "dx", "dw")):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * float(np.abs(b).max()),
                                   err_msg=name)


@pytest.mark.parametrize("S, tile", [(2048, 1024), (1536, 512), (768, 256)])
def test_every_tile_border_carries_its_halo(S, tile):
    """Several tiles a row: the rows round every border equal the XLA
    form's to the bit in the forward and in dx (K - 1 rows on each side are
    the ones a halo feeds), and dw sums over all tiles and both rows."""
    x, w, dy = _inputs(S, 4, "float32")
    got = _three(lambda x, w: causal_conv.causal_conv_pallas(x, w, True),
                 x, w, dy)
    want = _three(linear_attention._causal_conv(True), x, w, dy)
    assert causal_conv._largest(causal_conv.TILES, S) == tile
    borders = np.concatenate([np.arange(b - 4, b + 4)
                              for b in range(tile, S, tile)])
    for a, b in zip(got[:2], want[:2]):
        assert float(jnp.abs(b[:, borders]).min()) > 0
        np.testing.assert_allclose(a[:, borders], b[:, borders], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-4)


def test_rows_before_the_start_and_after_the_end_are_zeros():
    """An impulse at the first and at the last position: nothing leaks in
    from the clamped halo block or from the other row of the batch."""
    x = jnp.zeros((2, 512, C)).at[0, 0].set(1.0).at[1, -1].set(1.0)
    w = jnp.tile(jnp.arange(1.0, 5.0), (C, 1))
    y = causal_conv.causal_conv_pallas(x, w, False)
    np.testing.assert_array_equal(y[0, :4, 0], [4.0, 3.0, 2.0, 1.0])
    assert float(jnp.abs(y[0, 4:]).max()) == 0.0
    np.testing.assert_array_equal(y[1, -1, 0], 4.0)
    assert float(jnp.abs(y[1, :-1]).max()) == 0.0
    dx = jax.grad(lambda x: jnp.sum(
        causal_conv.causal_conv_pallas(x, w, False) * x))(x)
    np.testing.assert_array_equal(dx[1, -4:, 0], [1.0, 2.0, 3.0, 8.0])


@pytest.mark.parametrize("shape, K, dtype, want", [
    ((2, 512, 256), 4, "bfloat16", True), ((1, 256, 128), 2, "float32", True),
    ((2, 100, 96), 4, "float32", False),        # the toy models' shapes
    ((2, 512, 96), 4, "float32", False),        # C off the lane tile
    ((2, 100, 128), 4, "float32", False),       # no tile divides S
    ((2, 512, 128), 1, "float32", False), ((2, 512, 128), 10, "float32", False),
    ((2, 512, 128), 4, "float16", False), ((512, 128), 4, "float32", False)])
def test_supported_shapes(shape, K, dtype, want):
    assert causal_conv.supported(shape, (shape[-1], K), dtype) is want


def test_without_tpu_or_interpreter_nothing_is_supported(monkeypatch):
    monkeypatch.setattr(causal_conv, "supports_pallas", lambda: False)
    assert not causal_conv.supported((2, 512, 256), (256, 4), "bfloat16")


def _count(impl):
    return monitor.counter("conv_dispatch_total",
                           labels={"impl": impl}).value


def _run_op(S, channels, act="swish"):
    """One training step of a program that holds the op, through
    ``Executor.run``; returns the loss, the step's regions and its
    compiled text."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [S, channels])
        scale = fluid.layers.create_parameter([channels], "float32",
                                              name="x_scale")
        y = fluid.layers.causal_conv1d(
            x * scale, 4, param_attr=fluid.ParamAttr(name="conv_w"), act=act)
        loss = fluid.layers.mean(y * y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    assert [op.type for op in main.global_block().ops].count("swish") == 0
    feed = {"x": np.random.RandomState(0).randn(2, S, channels)
            .astype("float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        out, = exe.run(main, feed=feed, fetch_list=[loss])
        regions = profiler.newest_step_regions()
        fn, specs = profiler._NEWEST_STEP
    assert np.isfinite(out).all()
    return float(out), regions, fn.lower(*specs).compile().as_text()


def test_a_toy_shape_takes_the_xla_form_and_says_so():
    before = {i: _count(i) for i in ("xla", "pallas", "pallas_bwd")}
    _, _, text = _run_op(100, 96)
    assert _count("xla") > before["xla"]
    assert _count("pallas") == before["pallas"]
    assert _count("pallas_bwd") == before["pallas_bwd"]
    assert "conv_silu" not in text


def test_a_lane_wide_shape_takes_the_kernels_filed_under_the_op():
    """C = 128, S = 256: the forward kernel's instructions are filed under
    (forward, causal_conv1d), the backward kernel's under (backward,
    causal_conv1d) - where ``gdn_share_pct.train`` reads them."""
    before = {i: _count(i) for i in ("xla", "pallas", "pallas_bwd")}
    _, regions, text = _run_op(256, 128)
    assert _count("pallas") > before["pallas"]
    assert _count("pallas_bwd") > before["pallas_bwd"]
    assert _count("xla") == before["xla"]
    op_names = profiler.op_names_of(text)
    for kernel, phase in (("conv_silu_fwd", "forward"),
                          ("conv_silu_bwd", "backward")):
        filed = {regions[i] for i, name in op_names.items()
                 if "/%s/" % kernel in name and i in regions}
        assert filed == {(phase, "causal_conv1d")}, (kernel, filed)


@pytest.mark.parametrize("act", [None, "swish"])
def test_both_implementations_train_the_same_step(act, monkeypatch):
    """The same program and feed through the kernels and through the XLA
    form: one loss."""
    with_kernels = _run_op(256, 128, act)[0]
    monkeypatch.setattr(causal_conv, "supports_pallas", lambda: False)
    np.testing.assert_allclose(with_kernels, _run_op(256, 128, act)[0],
                               rtol=1e-6)


def test_calls_of_a_signature_are_built_once():
    x, w, _ = _inputs(512, 4, "bfloat16")
    first = causal_conv._call("fwd", x, w, True)
    assert causal_conv._call("fwd", x, w, True) is first
    assert causal_conv._call("fwd", x, w, False) is not first
