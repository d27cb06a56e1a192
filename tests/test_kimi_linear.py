"""Kimi Linear's block through ``fluid.layers`` and ``Executor.run`` against
the plain float32 reference (``benchmark/families/kimi_linear_train.py``,
which imports nothing of the program): the five-layer block's loss and
every leaf's gradient over three Adam steps under a planted router bias;
the chunked channel-gated delta rule against the position-by-position
recurrence; its Pallas kernels under the interpreter against the chunked
form; attention at two widths against the one-pass reference; the sigmoid
router; and the shares test - the parts that 32 expert-parallel ranks
compute plus the shared expert ONCE add up to the uncut layer."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TOY = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=5,
    linear_attn_config={"full_attn_layers": [4], "head_dim": 8,
                        "kda_layers": [1, 2, 3, 5], "num_heads": 2,
                        "short_conv_kernel_size": 4},
    num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, rms_norm_eps=1e-5,
    first_k_dense_replace=1, intermediate_size=48, num_experts=4,
    num_experts_total=16, expert_offset=4, num_experts_per_token=3,
    num_shared_experts=1, moe_intermediate_size=16, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    initializer_range=0.02, kda_chunk_size=16, learning_rate=1e-3, amp="off")
MIX = dict(batch=2, seq_len=40, recompute=False)    # 40: not a chunk multiple


def _family():
    import run as harness

    return harness.load_module("families", "kimi_linear_train")


@pytest.fixture(scope="module")
def both_sides():
    """Three Adam steps of the program (through ``Executor.run``) and of
    the reference, from one seed, under a router bias that changes the
    choice in most rows: losses, the first gradient of every leaf (the
    program's read back from Adam's first moment), and every leaf after
    the three steps."""
    import compare

    fam = _family()
    with jax.default_matmul_precision("highest"):
        step = fam.build(TOY, MIX)
        # the step donates its state: the reference draws its own copy
        step.set_params(fam.init_params(TOY, 11))
        params = fam.init_params(TOY, 11)
        bias = 0.05 * np.random.default_rng(3).standard_normal(
            (TOY["num_hidden_layers"], TOY["num_experts_total"]))
        feeds = [dict(f, router_bias=bias.astype("float32"))
                 for f in fam.feeds(TOY, MIX, 11, compare.STEPS)]
        got = {"loss": [], "step": step}
        for i, feed in enumerate(feeds):
            got["loss"].append(float(np.asarray(step.run(feed)).ravel()[0]))
            if i == 0:
                got["grad"] = {k: np.asarray(v) / (1.0 - 0.9)
                               for k, v in step.first_moments().items()}
        got["params"] = {k: np.asarray(v) for k, v in step.params().items()}

        loss_fn = fam.reference_loss(TOY, compare.matmul("f32"))
        value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
        opt = fam.optimizer(TOY)
        p = dict(params)
        m = {k: jnp.zeros_like(v) for k, v in p.items()}
        v2 = {k: jnp.zeros_like(v) for k, v in p.items()}
        ref = {"loss": []}
        for i, feed in enumerate(feeds):
            loss, g = value_and_grad(p, feed)
            ref["loss"].append(float(loss))
            if i == 0:
                ref["grad"] = {k: np.asarray(x) for k, x in g.items()}
                ref["unbiased"] = float(value_and_grad(p, dict(
                    feed, router_bias=0.0 * feed["router_bias"]))[0])
            t = i + 1
            lr_t = opt["lr"] * np.sqrt(1 - opt["beta2"] ** t) \
                / (1 - opt["beta1"] ** t)
            m = {k: opt["beta1"] * m[k] + (1 - opt["beta1"]) * g[k]
                 for k in p}
            v2 = {k: opt["beta2"] * v2[k] + (1 - opt["beta2"]) * g[k] ** 2
                  for k in p}
            p = {k: p[k] - lr_t * m[k] / (jnp.sqrt(v2[k]) + opt["epsilon"])
                 for k in p}
        ref["params"] = {k: np.asarray(x) for k, x in p.items()}
    return got, ref


def test_losses_match_reference_over_three_steps(both_sides):
    got, ref = both_sides
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=2e-5)
    # the planted bias changed the routing: it is under test
    assert abs(ref["unbiased"] - ref["loss"][0]) > 1e-6 * ref["loss"][0]


_KINDS = sorted({k.split("_", 2)[2] if k.startswith("layer_") else k
                 for k in _family().param_shapes(TOY)})


@pytest.mark.parametrize("kind", _KINDS)
def test_every_leafs_gradient_and_update_match_reference(both_sides, kind):
    """Each leaf of this kind, in every layer that has one: the first
    gradient element by element, and the leaf after three Adam steps."""
    got, ref = both_sides
    leaves = [k for k in ref["grad"]
              if k == kind or (k.startswith("layer_")
                               and k.split("_", 2)[2] == kind)]
    assert leaves
    for k in leaves:
        scale = np.abs(ref["grad"][k]).max()
        assert scale > 0, k
        np.testing.assert_allclose(got["grad"][k], ref["grad"][k],
                                   rtol=2e-3, atol=2e-4 * scale, err_msg=k)
        # Adam's first steps move every element by ~lr whatever the
        # gradient's size, so a near-zero gradient's sign decides: hold
        # the elements whose gradient is not noise
        clear = np.abs(ref["grad"][k]) > 1e-3 * scale
        np.testing.assert_allclose(
            got["params"][k][clear], ref["params"][k][clear],
            rtol=1e-3, atol=2e-4, err_msg=k)


def test_the_router_bias_is_frozen_and_the_five_layer_kinds_are_there(
        both_sides):
    """No Adam state for the selection-only bias; layer 0 has the dense
    MLP, layer 3 latent attention, the others KDA and experts."""
    step = both_sides[0]["step"]
    trained = {op.input("Param")[0]
               for op in step.main.global_block().ops if op.type == "adam"}
    assert "layer_1_moe_router_bias" not in trained
    assert step.scope.find_var("layer_1_moe_router_bias").shape == (16,)
    for name in ("layer_0_kda_qkv_w", "layer_0_mlp_gate_w",
                 "layer_3_mla_kv_b_w", "layer_4_kda_dt_bias",
                 "layer_4_moe_shared_down_w"):
        assert name in trained, name
    assert "layer_0_moe_router_w" not in trained
    assert step.scope.find_var("layer_2_kda_dt_bias").shape == (16,)
    assert step.scope.find_var("layer_2_kda_a_log").shape == (2,)


def test_new_counters_say_which_implementation_was_traced(both_sides):
    from paddle_tpu.fluid import monitor

    assert monitor.counter("gdn_dispatch_total",
                           labels={"impl": "kda_chunked"}).value > 0
    assert monitor.counter("moe_route_dispatch_total",
                           labels={"scoring": "sigmoid"}).value > 0
    assert monitor.counter("conv_dispatch_total",
                           labels={"impl": "xla"}).value > 0


def test_the_convolutions_carry_their_activation_and_no_swish_op_is_left(
        both_sides):
    ops = both_sides[0]["step"].main.global_block().ops
    convs = [op for op in ops if op.type == "causal_conv1d"]
    assert len(convs) == 4      # the KDA layers of the toy's five
    assert all(op.attrs["activation"] == "swish" for op in convs)
    assert not [op for op in ops if op.type == "swish"]


def test_config_takes_published_names_and_startup_draws_as_it_says():
    """``from_dict`` passes over a file's notes, a keyword the class lacks
    is refused, the router reads the names the other decoders publish, and
    the startup program draws every matrix at ``initializer_range`` and the
    selection-only bias at zero, frozen."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import kimi_linear

    cfg = kimi_linear.KimiLinearConfig.from_dict(
        dict(TOY, vocab_size=2048, assumed={"a note": "shapes no step"}))
    assert not hasattr(cfg, "assumed")
    with pytest.raises(TypeError, match="KimiLinearConfig has no key"):
        kimi_linear.KimiLinearConfig(embedding_std=1.0)
    assert cfg.num_experts_per_tok == 3 and cfg.norm_topk_prob is True
    assert [cfg.is_full_attention(i) for i in range(5)] == [
        False, False, False, True, False]
    main, startup, _ = kimi_linear.build_train_program(cfg, 1, 16,
                                                       use_amp=False)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    assert abs(float(np.std(scope.find_var("embed_tokens"))) - 0.02) < 0.002
    assert abs(float(np.std(scope.find_var("lm_head_w"))) - 0.02) < 0.002
    assert float(np.abs(scope.find_var("layer_1_moe_router_bias")).max()) == 0
    updated = {op.input("Param")[0] for op in main.global_block().ops
               if op.type == "adam"}
    assert "layer_1_moe_router_w" in updated
    assert "layer_1_moe_router_bias" not in updated


# -- the channel-gated delta rule ----------------------------------------------
def _recurrence(q, k, v, g, beta):
    """The channel-gated delta rule, one position at a time."""
    B, S, H, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        decayed = state * jnp.exp(g_t)[..., None]
        delta = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", decayed, k_t))
        state = decayed + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _kda_inputs(S, B=2, H=3, dk=16, dv=24, seed=5):
    """Head 0's gate is -1.6 at every position and channel: -102 over a
    chunk of 64, so ``exp(-Gc)`` alone is past float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -1.6 * jax.random.uniform(ks[3], (B, S, H, dk))
    g = g.at[:, :, 0].set(-1.6)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


def _split_form(q, k, v, g, beta, C=64):
    """The chunked form with ``exp(Gc)`` and ``exp(-Gc)`` apart, as the
    scalar rule's algebra would be copied: what must NOT be written."""
    B, S, H, dk = q.shape
    gc = jnp.cumsum(g.reshape(B, S // C, C, H, dk), axis=2)
    kd = k.reshape(gc.shape) * jnp.exp(-gc)
    qd = q.reshape(gc.shape) * jnp.exp(gc)
    return jnp.einsum("bnihd,bnjhd->bnhij", qd, kd)


@pytest.mark.parametrize("S", [64, 150])     # 150: not a chunk multiple
def test_chunked_channel_gated_rule_matches_the_recurrence(S):
    from paddle_tpu.fluid.ops.linear_attention import kda_chunked

    args = _kda_inputs(S)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(_recurrence)(*args)
        got = jax.jit(functools.partial(kda_chunked, chunk_size=64))(*args)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)
        if S % 64 == 0:     # the gates are strong enough to tell
            assert not bool(jnp.isfinite(_split_form(*args)).all())


def test_chunked_channel_gated_rules_gradients_match_the_recurrences():
    from paddle_tpu.fluid.ops.linear_attention import kda_chunked

    args = _kda_inputs(80, B=1, H=2)
    grad = lambda fn: jax.jit(jax.grad(                         # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)))
    with jax.default_matmul_precision("highest"):
        got = grad(functools.partial(kda_chunked, chunk_size=32))(*args)
        want = grad(_recurrence)(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)


def test_one_gate_for_all_channels_is_the_scalar_rule():
    from paddle_tpu.fluid.ops.linear_attention import (
        gated_delta_rule_chunked, kda_chunked)

    q, k, v, g, beta = _kda_inputs(100)
    gs = 0.1 * g[..., 0]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            jax.jit(functools.partial(kda_chunked, chunk_size=32))(
                q, k, v, jnp.broadcast_to(gs[..., None], g.shape), beta),
            jax.jit(functools.partial(gated_delta_rule_chunked,
                                      chunk_size=32))(q, k, v, gs, beta),
            rtol=1e-4, atol=2e-6)


@pytest.fixture(scope="module")
def kernel_and_oracle():
    """The kernels under the interpreter and the chunked XLA form at head
    dim 128, S = 200 (padded to 256: two groups): o and every cotangent."""
    from paddle_tpu.fluid.ops.linear_attention import kda_chunked
    from paddle_tpu.kernels import delta_rule

    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    try:
        args = _kda_inputs(200, B=1, H=2, dk=128, dv=128, seed=9)
        assert delta_rule.supported(128, 128, 64)
        out = {}
        for name, fn in (
                ("kernel", functools.partial(
                    delta_rule.gated_delta_rule_pallas, chunk_size=64)),
                ("oracle", functools.partial(kda_chunked, chunk_size=64))):
            def both(*a, fn=fn):
                o, vjp = jax.vjp(fn, *a)
                return (o,) + vjp(jnp.cos(o))

            out[name] = jax.jit(both)(*args)
    finally:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    return out


@pytest.mark.parametrize("i,what", list(enumerate(
    "o dq dk dv dg dbeta".split())))
def test_kda_kernels_match_the_chunked_form(kernel_and_oracle, i, what):
    got, want = (kernel_and_oracle[n][i] for n in ("kernel", "oracle"))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()),
                               err_msg=what)


def test_kda_kernel_dispatch_is_counted(kernel_and_oracle):
    from paddle_tpu.fluid import monitor

    for impl in ("kda_pallas", "kda_pallas_bwd"):
        assert monitor.counter("gdn_dispatch_total",
                               labels={"impl": impl}).value > 0, impl


def test_the_op_takes_the_gate_a_channel_and_keeps_the_scalar_form():
    """``A`` [B, S, H, dk] with ``DtBias`` [H * dk] against the recurrence
    under the op's own activations; ``A`` [B, S, H] still builds [H]."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    B, S, H, d = 1, 24, 2, 8
    rng = np.random.default_rng(0)
    vals = {n: rng.standard_normal(s).astype("float32") for n, s in (
        ("q", (B, S, H, d)), ("k", (B, S, H, d)), ("v", (B, S, H, d)),
        ("a", (B, S, H, d)), ("b", (B, S, H)))}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ins = {n: layers.data(n, shape=list(x.shape),
                              append_batch_size=False)
               for n, x in vals.items()}
        out = layers.gated_delta_rule(
            ins["q"], ins["k"], ins["v"], ins["a"], ins["b"],
            a_log_attr=fluid.ParamAttr(name="a_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"), chunk_size=16)
        layers.gated_delta_rule(
            ins["q"], ins["k"], ins["v"], ins["b"], ins["b"],
            a_log_attr=fluid.ParamAttr(name="a_log_s"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias_s"), chunk_size=16)
    scope = fluid.Scope()
    a_log = rng.uniform(0.0, 2.0, (H,)).astype("float32")
    dt_bias = rng.standard_normal((H * d,)).astype("float32")
    with fluid.scope_guard(scope), jax.default_matmul_precision("highest"):
        exe = fluid.Executor()
        exe.run(startup)
        assert scope.find_var("dt_bias").shape == (H * d,)
        assert scope.find_var("dt_bias_s").shape == (H,)
        scope.set_var("a_log", jnp.array(a_log))
        scope.set_var("dt_bias", jnp.array(dt_bias))
        (got,) = exe.run(main, feed=vals, fetch_list=[out])
        l2 = lambda x: x * jax.lax.rsqrt(                       # noqa: E731
            jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            vals["a"] + dt_bias.reshape(H, d))
        want = _recurrence(l2(vals["q"]) * d ** -0.5, l2(vals["k"]),
                           vals["v"], g, jax.nn.sigmoid(vals["b"]))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


# -- attention at two widths ---------------------------------------------------
@pytest.fixture(scope="module")
def two_widths():
    """Causal attention with 192-wide q and k and 128-wide v at S = 256 (two
    tiles of 128) under the interpreter, and the one-pass reference."""
    from paddle_tpu.fluid import monitor
    from paddle_tpu.kernels import attention as A

    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    try:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k = (jax.random.normal(ks[i], (1, 2, 256, 192)) for i in (0, 1))
        v, do = (jax.random.normal(ks[i], (1, 2, 256, 128)) for i in (2, 3))
        tiers0 = {t: monitor.counter("attn_kernel_dispatch_total",
                                     labels={"tier": t}).value
                  for t in A.KERNEL_TIERS}
        out = {}
        for name, fn in (
                ("kernel", lambda q, k, v: A.fused_attention(
                    q, k, v, scale=192 ** -0.5, causal=True)),
                ("oracle", lambda q, k, v: A._ref_attention(
                    q, k, v, None, 192 ** -0.5, 0.0, None, True))):
            o, vjp = jax.vjp(fn, q, k, v)
            out[name] = (o,) + vjp(do)
        out["tiers"] = {t: n for t in A.KERNEL_TIERS if (n := monitor.counter(
            "attn_kernel_dispatch_total", labels={"tier": t}).value
            - tiers0[t])}
        # equal widths, and the same call with v's first 64 columns only
        bias, seed = jnp.zeros((1, 1, 1, 256)), jnp.zeros((1,), jnp.int32)
        k128 = k[..., :128]
        out["equal"] = A._pallas_attention_flash(
            q[..., :128], k128, v, bias, 0.1, 0.0, seed, True)
        out["narrow"] = A._pallas_attention_flash(
            q[..., :128], k128, v[..., :64], bias, 0.1, 0.0, seed, True)
    finally:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    return out


@pytest.mark.parametrize("i,what", list(enumerate("o dq dk dv".split())))
def test_attention_at_two_widths_matches_the_reference(two_widths, i, what):
    got, want = (two_widths[n][i] for n in ("kernel", "oracle"))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5, err_msg=what)


def test_two_widths_take_the_flash_tier_and_equal_widths_are_its_bits(
        two_widths):
    """At S = 256 equal widths take the block tier; a narrower v is the
    flash tier's, and its output columns are the equal-width kernel's own,
    to the bit (a column of P V knows no other column), as is the row
    logsumexp."""
    assert two_widths["tiers"] == {"flash": 1, "flash_bwd": 1}
    (o, lse), (o64, lse64) = two_widths["equal"], two_widths["narrow"]
    assert o.shape[-1] == 128 and o64.shape[-1] == 64
    np.testing.assert_array_equal(o[..., :64], o64)
    np.testing.assert_array_equal(lse, lse64)


def test_blockwise_scan_takes_two_widths():
    from paddle_tpu.kernels import attention as A

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k = (jax.random.normal(ks[i], (1, 2, 1100, 24)) for i in (0, 1))
    v = jax.random.normal(ks[2], (1, 2, 1100, 16))
    bias = jnp.zeros((1, 1, 1, 1100))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            A._blockwise_attention(q, k, v, bias, 0.2, 0.0, None, True),
            A._ref_attention(q, k, v, bias, 0.2, 0.0, None, True),
            rtol=1e-4, atol=1e-5)


# -- the router and the shares --------------------------------------------------
def _route(x, w, k, **kw):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    bias = kw.pop("bias", None)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), append_batch_size=False)
        if bias is not None:
            kw["bias_attr"] = fluid.ParamAttr(name="bias", trainable=False)
        ids, wts = layers.moe_route(
            xv, w.shape[1], k, param_attr=fluid.ParamAttr(name="w"), **kw)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        scope.set_var("w", jnp.array(w))
        if bias is not None:
            scope.set_var("bias", jnp.array(bias))
        return exe.run(main, feed={"x": x}, fetch_list=[ids, wts])


@pytest.mark.parametrize("planted", [False, True],
                         ids=["no_bias", "planted_bias"])
def test_sigmoid_router_bias_changes_the_choice_and_not_the_weights(planted):
    """``Bias`` is optional under ``sigmoid``: without it the k largest
    scores are chosen; with it the k largest of score + bias, and the
    weights are the chosen experts' own scores either way."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((40, 16)).astype("float32")
    w = rng.standard_normal((16, 32)).astype("float32")
    bias = None
    if planted:
        bias = np.zeros(32, "float32")
        bias[5] = 10.0                  # expert 5 is chosen by every token
    score = np.asarray(jax.nn.sigmoid(jnp.matmul(x, w, precision="highest")))
    ids, wts = _route(x, w, 4, scoring="sigmoid", bias=bias,
                      routed_scaling_factor=2.446)
    choice = score + bias if planted else score
    want_ids = np.argsort(-choice, axis=1, kind="stable")[:, :4]
    assert (np.sort(ids, 1) == np.sort(want_ids, 1)).all()
    own = np.take_along_axis(score, ids, 1)         # never score + bias
    np.testing.assert_allclose(
        wts, own / (own.sum(1, keepdims=True) + 1e-20) * 2.446, rtol=1e-6)
    assert (ids == 5).any(axis=1).all() == planted


def test_softmax_router_is_todays_bit_for_bit():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((40, 16)).astype("float32")
    w = rng.standard_normal((16, 32)).astype("float32")
    ids, wts = _route(x, w, 4)
    p = jax.nn.softmax(jnp.matmul(x, w, precision="highest"), axis=-1)
    vals, want = jax.lax.top_k(p, 4)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_array_equal(
        wts, vals / jnp.sum(vals, axis=-1, keepdims=True))


def test_thirty_two_ranks_shares_and_one_shared_expert_add_up_to_the_layer():
    """256 experts under top-8: 32 ranks of 8 (offsets 0-248), each through
    ``decoder_blocks.routed_experts`` with ITS config, plus the shared
    expert ONCE, against the uncut layer computed densely."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.models import decoder_blocks, kimi_linear

    T, h, f, E, k, held = 24, 16, 8, 256, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    x = jax.random.normal(ks[0], (1, T, h))
    router = jax.random.normal(ks[1], (h, E))
    wg, wu = (0.3 * jax.random.normal(ks[i], (E, h, f)) for i in (2, 3))
    wd = 0.3 * jax.random.normal(ks[4], (E, f, h))
    sg, su = (0.3 * jax.random.normal(ks[i], (h, f)) for i in (5, 6))
    sd = 0.3 * jax.random.normal(ks[7], (f, h))
    bias = 0.05 * np.random.default_rng(1).standard_normal(E).astype("float32")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=[1, T, h], append_batch_size=False)
        parts = []
        for r in range(E // held):
            cfg = kimi_linear.KimiLinearConfig.from_dict(dict(
                TOY, hidden_size=h, moe_intermediate_size=f, num_experts=held,
                num_experts_total=E, num_experts_per_token=k,
                expert_offset=r * held))
            parts.append(decoder_blocks.routed_experts(xv, cfg, "r%d" % r))
        shared = kimi_linear._mlp(xv, f, cfg, "shared")
    scope = fluid.Scope()
    with fluid.scope_guard(scope), jax.default_matmul_precision("highest"):
        exe = fluid.Executor()
        exe.run(startup)
        for r in range(E // held):
            sl = slice(r * held, (r + 1) * held)
            for name, val in (("router_w", router), ("router_bias", bias),
                              ("gate_w", wg[sl]), ("up_w", wu[sl]),
                              ("down_w", wd[sl])):
                scope.set_var("r%d_%s" % (r, name), jnp.array(val))
        for name, val in (("gate", sg), ("up", su), ("down", sd)):
            scope.set_var("shared_%s_w" % name, jnp.array(val))
        outs = exe.run(main, feed={"x": np.asarray(x)},
                       fetch_list=parts + [shared])
        x2 = x[0]
        score = jax.nn.sigmoid(x2 @ router)
        _, top = jax.lax.top_k(score + bias, k)
        chosen = jnp.sum(jax.nn.one_hot(top, E), 1)
        w = score * chosen / jnp.sum(score * chosen, -1, keepdims=True) \
            * 2.446
        mlp = lambda g, u, d: (jax.nn.silu(x2 @ g) * (x2 @ u)) @ d  # noqa: E731
        uncut = sum(w[:, e:e + 1] * mlp(wg[e], wu[e], wd[e])
                    for e in range(E)) + mlp(sg, su, sd)
    assert len(outs) == 33
    assert sum(np.abs(o).max() > 0 for o in outs[:32]) > 16
    np.testing.assert_allclose((sum(outs[:32]) + outs[32])[0], uncut,
                               rtol=1e-4, atol=1e-5)
