"""The gated delta rule's Pallas kernels (kernels/delta_rule.py), run
through the pallas interpreter on the CPU so the real kernel bodies
execute: forward and all five cotangents against the position-by-position
recurrence and against the XLA chunked form (the oracle that stays in the
tree), the state across a tile border, and which implementation the op's
lowering takes for which shapes."""

import functools
import os

import numpy as np
import pytest

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import monitor
from paddle_tpu.fluid.ops.linear_attention import gated_delta_rule_chunked
from paddle_tpu.kernels import delta_rule

D = 128         # the kernels' head dim: whole lane tiles
NAMES = "q k v g beta".split()


def _recurrence(q, k, v, g, beta):
    """One position at a time, f32: the definition."""
    B, S, H, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        decayed = state * jnp.exp(g_t)[..., None, None]
        delta = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", decayed, k_t))
        state = decayed + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _inputs(S, Hv, rep, dtype, decay, B=2):
    ks = jax.random.split(jax.random.PRNGKey(S + Hv), 5)
    Hk = Hv // rep
    q = jax.random.normal(ks[0], (B, S, Hk, D))
    k = jax.random.normal(ks[1], (B, S, Hk, D))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / 4.0
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, Hv, D))
    # "mild": g near 0 (the state is kept); "strong": g strongly negative
    scale = {"mild": 0.02, "strong": 4.0}[decay]
    g = -scale * jax.nn.softplus(jax.random.normal(ks[3], (B, S, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, Hv)))
    return tuple(t.astype(dtype) for t in (q, k, v)) + (g, beta)


def _wide(fn, rep):
    """``fn`` over q and k repeated to the value heads, inputs in f32."""
    def run(q, k, v, g, beta):
        q, k = (jnp.repeat(t.astype(jnp.float32), rep, axis=2)
                for t in (q, k))
        return fn(q, k, v.astype(jnp.float32), g, beta)
    return run


def _value_and_cotangents(fn, args):
    def loss(*a):
        o = fn(*a).astype(jnp.float32)
        return jnp.sum(jnp.sin(o)), o
    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(*args)
    return np.asarray(o), [np.asarray(t.astype(jnp.float32)) for t in grads]


# dtype, Hv / Hk (of 2 value heads: one key head with both, or two key heads
# in one grid step), S (256: whole groups; 200: a multiple of no chunk;
# 1100: past one tile, padded to two), chunk, decay
CASES = [
    ("float32", 1, 256, 64, "mild"),
    ("float32", 2, 200, 64, "strong"),
    ("float32", 2, 1100, 64, "mild"),
    ("float32", 1, 200, 32, "strong"),
    ("float32", 2, 256, 128, "mild"),
    ("bfloat16", 2, 256, 64, "strong"),
    ("bfloat16", 1, 200, 16, "mild"),
]
IDS = ["-".join(str(x) for x in case) for case in CASES]


@functools.lru_cache(maxsize=None)
def _sides(case):
    """(kernel, recurrence, chunked): each (o, five cotangents)."""
    dtype, rep, S, chunk, decay = case
    args = _inputs(S, 2, rep, jnp.dtype(dtype), decay)
    with jax.default_matmul_precision("highest"):
        got = _value_and_cotangents(
            lambda *a: delta_rule.gated_delta_rule_pallas(
                *a, chunk_size=chunk), args)
        rec = _value_and_cotangents(_wide(_recurrence, rep), args)
        chunked = _value_and_cotangents(_wide(functools.partial(
            gated_delta_rule_chunked, chunk_size=chunk), rep), args)
    return got, rec, chunked


def _tolerance(dtype):
    # bf16: the operands of every matmul are rounded to 8 bits, in the
    # kernel as in the chunked form; the f32 oracles are not
    return (2e-4, 2e-5) if dtype == "float32" else (6e-2, 3e-2)


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_forward_matches_recurrence_and_chunked_form(case):
    got, rec, chunked = _sides(case)
    rtol, atol = _tolerance(case[0])
    for want, name in ((rec, "recurrence"), (chunked, "chunked")):
        np.testing.assert_allclose(
            got[0], want[0], rtol=rtol,
            atol=atol * float(np.abs(want[0]).max()), err_msg=name)


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_five_cotangents_match_recurrence_and_chunked_form(case):
    got, rec, chunked = _sides(case)
    rtol, atol = _tolerance(case[0])
    for want, side in ((rec, "recurrence"), (chunked, "chunked")):
        for a, b, name in zip(got[1], want[1], NAMES):
            assert a.shape == b.shape, (name, a.shape, b.shape)
            np.testing.assert_allclose(
                a, b, rtol=rtol, atol=atol * float(np.abs(b).max()),
                err_msg="d%s against the %s" % (name, side))


def test_state_across_a_tile_border_equals_the_one_inside_a_tile(
        monkeypatch):
    """2048 positions are two tiles; with the tile doubled they are one,
    and the same groups run in the same order on the same numbers."""
    args = _inputs(2048, 2, 2, jnp.float32, "mild", B=1)
    fn = lambda *a: delta_rule.gated_delta_rule_pallas(  # noqa: E731
        *a, chunk_size=64)
    two = _value_and_cotangents(fn, args)
    monkeypatch.setattr(delta_rule, "TILE", 2048)
    one = _value_and_cotangents(fn, args)
    np.testing.assert_array_equal(two[0], one[0])
    for a, b, name in zip(two[1], one[1], NAMES):
        np.testing.assert_array_equal(a, b, err_msg=name)
    # and the second tile did see a state: its rows differ from a run
    # that starts there
    late = tuple(t[:, 1024:] for t in args)
    assert np.abs(_value_and_cotangents(fn, late)[0]
                  - two[0][:, 1024:]).max() > 1e-3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_l2norm_in_the_kernels_matches_the_callers(dtype):
    """``l2norm_eps``: raw q and k go in and the kernels normalise them
    (q also scaled), forward and backward, as the op's lowering does in
    XLA before the chunked form."""
    eps, dtype = 1e-6, jnp.dtype(dtype)
    q, k, v, g, beta = _inputs(200, 2, 2, jnp.float32, "mild")
    q, k = (3.0 * q).astype(dtype), (0.2 * k).astype(dtype)   # far from 1
    args = (q, k, v.astype(dtype), g, beta)

    def callers(q, k, v, g, beta):
        qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        qf = qf * jax.lax.rsqrt(jnp.sum(qf * qf, -1, keepdims=True) + eps)
        kf = kf * jax.lax.rsqrt(jnp.sum(kf * kf, -1, keepdims=True) + eps)
        qn, kn = (jnp.repeat(t.astype(dtype), 2, axis=2)
                  for t in (qf * D ** -0.5, kf))
        return gated_delta_rule_chunked(qn, kn, v, g, beta, chunk_size=64)

    with jax.default_matmul_precision("highest"):
        got = _value_and_cotangents(
            lambda *a: delta_rule.gated_delta_rule_pallas(
                *a, chunk_size=64, l2norm_eps=eps), args)
        want = _value_and_cotangents(callers, args)
    rtol, atol = _tolerance(dtype.name)
    np.testing.assert_allclose(got[0], want[0], rtol=rtol,
                               atol=atol * float(np.abs(want[0]).max()))
    for a, b, name in zip(got[1], want[1], NAMES):
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=atol * float(np.abs(b).max()),
            err_msg="d" + name)


def _count(impl):
    return monitor.counter("gdn_dispatch_total",
                           labels={"impl": impl}).value


def _run_op(d, S=128, Hk=1, Hv=2, chunk=64):
    """One training step of a program that holds the op, through
    ``Executor.run``: what the lowering dispatches to for these shapes."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data("q", [S, Hk, d])
        k = fluid.layers.data("k", [S, Hk, d])
        v = fluid.layers.data("v", [S, Hv, d])
        a = fluid.layers.data("a", [S, Hv])
        b = fluid.layers.data("b", [S, Hv])
        for t in (q, k, v, a, b):
            t.stop_gradient = False
        w = fluid.layers.create_parameter([d], "float32", name="w_scale")
        o = fluid.layers.gated_delta_rule(q, k, v * w, a, b,
                                          chunk_size=chunk)
        loss = fluid.layers.mean(o)
        fluid.optimizer.SGD(0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"q": rng.randn(2, S, Hk, d), "k": rng.randn(2, S, Hk, d),
            "v": rng.randn(2, S, Hv, d), "a": rng.randn(2, S, Hv),
            "b": rng.randn(2, S, Hv)}
    feed = {n: x.astype("float32") for n, x in feed.items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        out, = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(out).all()


def test_head_dim_64_takes_the_chunked_form_and_says_so():
    before = {i: _count(i) for i in ("chunked", "pallas", "pallas_bwd")}
    _run_op(64)
    assert _count("chunked") > before["chunked"]
    assert _count("pallas") == before["pallas"]
    assert _count("pallas_bwd") == before["pallas_bwd"]


def test_head_dim_128_takes_the_kernels_forward_and_backward():
    before = {i: _count(i) for i in ("chunked", "pallas", "pallas_bwd")}
    _run_op(128)
    assert _count("pallas") > before["pallas"]
    assert _count("pallas_bwd") > before["pallas_bwd"]
    assert _count("chunked") == before["chunked"]


@pytest.mark.parametrize("dk, dv, chunk, want", [
    (128, 128, 64, True), (128, 256, 16, True), (128, 128, 128, True),
    (64, 128, 64, False), (128, 96, 64, False), (128, 128, 48, False),
    (128, 128, 8, False), (128, 128, 256, False)])
def test_supported_shapes(dk, dv, chunk, want):
    assert delta_rule.supported(dk, dv, chunk) is want


def test_without_tpu_or_interpreter_nothing_is_supported(monkeypatch):
    monkeypatch.setattr(delta_rule, "supports_pallas", lambda: False)
    assert not delta_rule.supported(128, 128, 64)
